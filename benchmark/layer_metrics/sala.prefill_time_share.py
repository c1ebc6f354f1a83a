"""Share of the window's boundary time inside the engine's ``llm.prefill``
spans (the snapshot restored, admitted suffixes fed through the prefill
programs, up to the fetch of their first tokens), during which every
decoding slot waits (``dsv2.prefill_time_share``'s reading, in this
cell)."""

from benchmark.sala_stats import accepted_reader

read = accepted_reader("dsv2.prefill_time_share")
