"""Share of the traced window in which the device was idle while a Caption epoch re-tiered the KV and prefix pages (span engine.caption.actuate)."""
from bench.harness.program_spans import caption_actuate_idle as read  # noqa: F401
