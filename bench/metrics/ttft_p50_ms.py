"""Median time to first token over every request due in the window, from when it was due."""
from bench.harness.readers import ttft_p50_ms as read  # noqa: F401
