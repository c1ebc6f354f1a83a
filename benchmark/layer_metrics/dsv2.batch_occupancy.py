"""Slots that committed a token a decode step, of the engine's 128: the
closed loop keeps every slot taken, so what is missing is the boundary a
slot spends between one request's last token and the next one's prefill
(``generate.batch_occupancy``'s reading, in this cell)."""

from benchmark.docqa_stats import accepted_reader

read = accepted_reader("generate.batch_occupancy")
