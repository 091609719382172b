"""Device idle share under the engine's cohort spans in the generation
cell.  Moves serve_throughput."""
from bench import engine_readers

read = engine_readers.engine_idle
