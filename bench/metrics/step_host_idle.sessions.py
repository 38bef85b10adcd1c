"""Share of the traced window in which the device was idle while a decode step built its token batch, modelled its time or sampled and retired requests (spans engine.step.*)."""
from bench.harness.program_spans import step_host_idle as read  # noqa: F401
