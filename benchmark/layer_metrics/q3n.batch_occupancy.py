"""Slots that committed a token a decode step, of the engine's 128
(``generate.batch_occupancy``'s reading, in this cell): a slot whose turn
is still riding in, window by window, commits none."""

from benchmark.q3n_stats import accepted_reader

read = accepted_reader("generate.batch_occupancy")
