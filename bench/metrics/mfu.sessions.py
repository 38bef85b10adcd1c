"""Model FLOPs of the tokens computed in the traced window (prompt tokens replayed and tokens generated) over the window and the chip's bf16 peak."""
from bench.harness.readers import mfu_serving_pct as read  # noqa: F401
