"""``store.read_bytes_per_event``, read in the open-loop cell, where it moves
the staleness of late results."""

from harness import reader

read = reader("store.read_bytes_per_event")
