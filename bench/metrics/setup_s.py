"""Set-up seconds: process start to the window's opening (loading, weights, compiling, warm-up)."""
from bench.harness.readers import setup_s as read  # noqa: F401
