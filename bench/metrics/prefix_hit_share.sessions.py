"""Share of admitted prompt tokens served from the prefix cache in the window (engine counters)."""
from bench.harness.readers import prefix_hit_share as read  # noqa: F401
