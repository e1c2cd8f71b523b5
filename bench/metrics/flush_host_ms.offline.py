"""Per-layer metric flush_host_ms.offline: see ``bench.spans.flush_host_ms``."""
from bench.spans import flush_host_ms as read  # noqa: F401
