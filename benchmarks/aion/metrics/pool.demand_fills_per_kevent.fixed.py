"""``pool.demand_fills_per_kevent``, read in the open-loop cell, where it moves
the staleness of late results."""

from harness import reader

read = reader("pool.demand_fills_per_kevent")
