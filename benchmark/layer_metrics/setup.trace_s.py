"""Seconds of set-up JAX spent tracing and lowering the process's jitted
functions: Python's work, which a warm compile cache does not save (the
build ledger's ``trace_s`` and ``lower_s``)."""

from benchmark import setup_ledger


def read(ctx):
    return setup_ledger.total("trace_s", "lower_s")
