"""State snapshots restored over requests prefilled in the window
(``sala.state_restores_per_request``'s reading, in this cell): 1 where
every request found the system prompt's snapshot (state and tail of every
DeltaNet layer)."""

from benchmark.q3n_stats import accepted_reader

read = accepted_reader("sala.state_restores_per_request")
