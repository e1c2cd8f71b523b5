"""Per-layer metric admit_ms: see ``bench.spans.admit_ms``."""
from bench.spans import admit_ms as read  # noqa: F401
