"""Device idle share in the closed-loop generation cell.
Moves serve_throughput."""
from bench import readers

read = readers.device_idle
