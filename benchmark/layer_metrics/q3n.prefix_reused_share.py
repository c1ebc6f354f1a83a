"""Prompt tokens served from the prefix index over prompt tokens sent, of
the requests prefilled in the window (``dsv2.prefix_reused_share``'s
reading, in this cell): the system prompt's 1,024 of each prompt, where
its snapshot was there to restore."""

from benchmark.q3n_stats import accepted_reader

read = accepted_reader("dsv2.prefix_reused_share")
