"""Per-layer metric submit_us.offline: see ``bench.readers.submit_us``."""
from bench.readers import submit_us as read  # noqa: F401
