"""Roofline share of the Pallas conv launches in the ASPP cell.
Moves serve_p95_ms."""
from bench import readers

read = readers.conv_roofline
