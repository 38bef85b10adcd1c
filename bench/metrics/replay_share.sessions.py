"""Share of the decode program's calls in the window that replay a prompt token (engine counters)."""
from bench.harness.readers import replay_share as read  # noqa: F401
