"""Slots that committed a token a decode step, of the engine's 128
(``generate.batch_occupancy``'s reading, in this cell)."""

from benchmark.sala_stats import accepted_reader

read = accepted_reader("generate.batch_occupancy")
