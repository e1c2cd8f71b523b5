"""Per-layer metric query_p99_ms.server: see ``bench.readers.query_p99_ms``."""
from bench.readers import query_p99_ms as read  # noqa: F401
