"""Blocks of the latent pool referenced by live sequences over the blocks
the pool can hand out, mean over the window's boundaries
(``generate.pool_used_share``'s reading, in this cell)."""

from benchmark.kimi_stats import accepted_reader

read = accepted_reader("generate.pool_used_share")
