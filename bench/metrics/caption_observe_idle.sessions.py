"""Share of the traced window in which the device was idle while a Caption epoch drained migrations, billed its routes and took its decision (span engine.caption.observe)."""
from bench.harness.program_spans import caption_observe_idle as read  # noqa: F401
