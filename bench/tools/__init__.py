"""Chip-side tools that set the benchmark's numbers: the rate sweep of
an open-loop mix and the readings that the correctness limits come
from.  The benchmark's own runs never call them."""
