"""``fold.round_share``, read in the open-loop cell, where it moves the
staleness of late results."""

from harness import reader

read = reader("fold.round_share")
