"""Share of the traced window in which no operation ran on the device
(1 - union of operation intervals / window), in %."""
from bench.lib.measure import idle_share as read  # noqa: F401
