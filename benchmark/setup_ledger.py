"""What the readers of set-up by phase share: the build ledger the
program's compile tracker keeps from JAX's own compile events
(``mmlspark_tpu.obs.profile.compile_tracker.ledger()``), one entry a
jitted function of the process with the seconds it was traced, lowered
and in the backend, and whether the backend compiled it or loaded it
from the persistent cache. A reader runs after the window, so it sums
everything the process built before then; a steady window builds
nothing."""

from __future__ import annotations


def total(*fields: str):
    """The sum of these fields over the ledger's entries; nothing where
    the program keeps no ledger (the parent commit) or the sum is 0."""
    from mmlspark_tpu.obs.profile import compile_tracker
    ledger = getattr(compile_tracker, "ledger", None)
    if ledger is None:
        return None
    return sum(entry[f] for entry in ledger() for f in fields) or None
