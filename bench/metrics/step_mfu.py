"""Per-layer metric step_mfu: see ``bench.readers.step_mfu``."""
from bench.readers import step_mfu as read  # noqa: F401
