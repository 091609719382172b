"""Device idle share under the engine's cohort spans in the ASPP cell.
Moves serve_p95_ms."""
from bench import engine_readers

read = engine_readers.engine_idle
