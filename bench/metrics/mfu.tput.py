"""Useful FLOPs over the peak across the generation cell's window.
Moves serve_throughput."""
from bench import readers

read = readers.mfu_window
