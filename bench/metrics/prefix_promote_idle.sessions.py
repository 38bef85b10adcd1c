"""Share of the traced window in which the device was idle while the engine promoted a prompt's new pages into the shared prefix pool (span engine.prefix.promote)."""
from bench.harness.program_spans import prefix_promote_idle as read  # noqa: F401
