"""Mean device time of one run of the decode step program, from the
trace."""
from bench.lib.measure import step_ms as read  # noqa: F401
