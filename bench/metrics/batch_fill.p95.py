"""Filled share of the engine's launched slots in the ASPP cell.
Moves serve_p95_ms."""
from bench import readers

read = readers.batch_fill
