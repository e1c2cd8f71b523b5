"""Per-layer metric scatter_ms: see ``bench.spans.scatter_ms``."""
from bench.spans import scatter_ms as read  # noqa: F401
