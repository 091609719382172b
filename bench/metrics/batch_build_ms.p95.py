"""Mean host time of the engine's slot-batch build in the ASPP cell.
Moves serve_p95_ms."""
from bench import engine_readers

read = engine_readers.batch_build_ms
