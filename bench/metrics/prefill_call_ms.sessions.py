"""Device time per call of the engine's chunked prefill program in the traced window."""
from bench.harness.prefill import prefill_call_ms as read  # noqa: F401
