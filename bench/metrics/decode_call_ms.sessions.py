"""Device time per call of the engine's decode program in the traced window."""
from bench.harness.readers import decode_call_ms as read  # noqa: F401
