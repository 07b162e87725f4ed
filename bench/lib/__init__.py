"""Shared code of the chip benchmark: harness, trace reduction, peaks,
operation and byte counts, and the plain references."""
