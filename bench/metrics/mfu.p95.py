"""Useful FLOPs over the peak while the ASPP cell's launches run.
Moves serve_p95_ms."""
from bench import readers

read = readers.mfu_launch
