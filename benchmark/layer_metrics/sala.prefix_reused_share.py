"""Prompt tokens served from the prefix index over prompt tokens sent, of
the requests prefilled in the window (``dsv2.prefix_reused_share``'s
reading, in this cell): here a document counts only where the snapshot of
its state was there to restore."""

from benchmark.sala_stats import accepted_reader

read = accepted_reader("dsv2.prefix_reused_share")
