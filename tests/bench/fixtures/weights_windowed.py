"""The dense layout, with the program's window fields fixed by the
file: a configuration whose layers differ only by their attention
window."""
from bench.weights.dense import layout  # noqa: F401


def program_keys(c: dict) -> dict:
    return {"local_window": c["sliding_window"],
            "global_every": c["sliding_window_pattern"]}
