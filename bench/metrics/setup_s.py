"""Seconds from the harness's start until the window can begin: the
program's import, the weights, the engine, compilation (or loading it
from the cache) and the warm-up ticks."""


def read(r):
    return r.setup_s
