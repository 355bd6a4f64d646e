"""Chip benchmark of the hybrid radix sort (run: ``python3 -m bench.run``)."""
