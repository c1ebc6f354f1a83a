"""Token-expert pairs that landed on a held expert, per token per expert
layer, over the window (``moe.held_pairs_per_token``'s reading, in this
cell): with 128 of 512 experts held and 10 a token, 2.5 where routing is
spread evenly."""

from benchmark.q3n_stats import accepted_reader

read = accepted_reader("moe.held_pairs_per_token")
