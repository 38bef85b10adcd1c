"""Share of the traced window in which the device was idle while the engine matched a prompt in the prefix index, attached its pages and copied a partial page on write (span engine.prefix.attach)."""
from bench.harness.program_spans import prefix_attach_idle as read  # noqa: F401
