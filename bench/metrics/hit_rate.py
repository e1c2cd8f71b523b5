"""Per-layer metric hit_rate: see ``bench.readers.hit_rate``."""
from bench.readers import hit_rate as read  # noqa: F401
