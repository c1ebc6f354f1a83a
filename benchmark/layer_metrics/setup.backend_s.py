"""Seconds of set-up in the backend's build step: XLA compiling a
program, or the persistent cache loading it (the build ledger's
``backend_s``)."""

from benchmark import setup_ledger


def read(ctx):
    return setup_ledger.total("backend_s")
