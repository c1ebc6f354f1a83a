"""Programs the backend built before the reader ran, compiled or loaded:
the build ledger's count, which is the harness's ``compiles_in_setup +
compiles_in_window`` where the ledger listened from the first build."""

from benchmark import setup_ledger


def read(ctx):
    return setup_ledger.total("compiled", "loaded")
