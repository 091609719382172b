"""Device idle time under the engine's output fetch, per launch, in the
generation cell.  Moves serve_throughput."""
from bench import engine_readers

read = engine_readers.fetch_idle_ms
