"""Least time of the traced ticks' decode steps, each the larger of FLOPs
over peak FLOP/s and bytes over HBM bandwidth (``bench/counts``), over
the step's device time, in %.  ``run.py`` prints which bound wins."""
from bench.lib.measure import step_roofline as read  # noqa: F401
