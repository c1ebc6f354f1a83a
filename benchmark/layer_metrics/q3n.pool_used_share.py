"""Blocks referenced by live sequences, the system prompt's among them
(counted once however many slots chain them), over the blocks the pool can
hand out, mean over the window's boundaries (``generate.pool_used_share``'s
reading, in this cell)."""

from benchmark.q3n_stats import accepted_reader

read = accepted_reader("generate.pool_used_share")
