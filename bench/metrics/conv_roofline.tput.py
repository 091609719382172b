"""Roofline share of the Pallas conv launches in the generation cell.
Moves serve_throughput."""
from bench import readers

read = readers.conv_roofline
