"""Per-layer metric submit_us.server: see ``bench.readers.submit_us``."""
from bench.readers import submit_us as read  # noqa: F401
