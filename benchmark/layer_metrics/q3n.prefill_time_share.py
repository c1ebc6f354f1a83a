"""Share of the window's boundary time inside the engine's ``llm.prefill``
spans (the host's part of the window that rides: the snapshot restored,
the window's tokens and tables made; ``dsv2.prefill_time_share``'s
reading, in this cell)."""

from benchmark.q3n_stats import accepted_reader

read = accepted_reader("dsv2.prefill_time_share")
