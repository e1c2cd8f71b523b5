"""Per-layer metric fetch_ms: see ``bench.spans.fetch_ms``."""
from bench.spans import fetch_ms as read  # noqa: F401
