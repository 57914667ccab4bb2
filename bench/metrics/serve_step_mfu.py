"""FLOPs the traced ticks' live slots need (``bench/counts``) over the
decode step's device time times the chip's peak, in %."""
from bench.lib.measure import step_mfu as read  # noqa: F401
