"""Least time per decode-program call (weights plus live KV at peak bandwidth, or FLOPs at peak) over its device time."""
from bench.harness.readers import decode_roofline_pct as read  # noqa: F401
