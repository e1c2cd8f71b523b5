"""Per-layer metric tbe_roofline.offline: see ``bench.readers.tbe_roofline``."""
from bench.readers import tbe_roofline as read  # noqa: F401
