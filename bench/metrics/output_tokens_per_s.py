"""Output tokens served by the ticks of the window over the time those
ticks took, first start to last end."""
from bench.lib.measure import window_ticks


def read(r):
    t = window_ticks(r)
    if not len(t):
        return None
    return float(t[:, 3].sum() / (t[-1, 1] - t[0, 0]))
