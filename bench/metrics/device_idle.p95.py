"""Device idle share in the open-loop ASPP cell.
Moves serve_p95_ms."""
from bench import readers

read = readers.device_idle
