"""Blocks referenced by live sequences, the resident documents' among
them (each counted once however many slots chain it), over the blocks the
pool can hand out, mean over the window's boundaries
(``generate.pool_used_share``'s reading, in this cell)."""

from benchmark.sala_stats import accepted_reader

read = accepted_reader("generate.pool_used_share")
