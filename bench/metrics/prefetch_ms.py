"""Per-layer metric prefetch_ms: see ``bench.readers.prefetch_ms``."""
from bench.readers import prefetch_ms as read  # noqa: F401
