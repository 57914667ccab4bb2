"""Device time per tick of every program other than the decode step (the
cache wipes on admission, small input conversions), from the trace."""
from bench.lib.measure import eager_ms_per_tick as read  # noqa: F401
