"""``device.memory_peak_gib``, read in the open-loop cell."""

from harness import reader

read = reader("device.memory_peak_gib")
