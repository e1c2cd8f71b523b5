"""Per-layer metric rw_collective_ms: see ``bench.readers.rw_collective_ms``."""
from bench.readers import rw_collective_ms as read  # noqa: F401
