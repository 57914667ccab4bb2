"""A whole run at a small size on the CPU (everything after the harness's
look for a chip), with the timed path sound and then broken underneath:
``correct`` comes out true for the sound program and false for each
fault that a serving cell can have."""
import jax.numpy as jnp
import pytest

from bench import run

TINY = run.ROOT / "tests/bench/fixtures/tiny-dense.json"
MIX = {"kind": "serve", "driver": "backlog", "ramp_s": 0.5, "block": 16, "check_requests": 4,
       "prompt": {"median": 20, "sigma": 0.5, "min": 4, "max": 48},
       "output": {"median": 16, "sigma": 0.5, "min": 4, "max": 40}}
# the widest sound gap at this size is under 0.01 (seeds 11 and 12 read
# 0.00015 and 0.0077); each fault reads above 0.78
LIMITS = {"max_logit_gap": 0.1, "min_tokens_compared": 20}


def faulty(kind):
    from repro.runtime.steps import make_serve_step

    def make(cfg):
        step = make_serve_step(cfg)

        def serve_step(params, tokens, cache, index):
            nxt, new = step(params, tokens, cache, index)
            b = nxt.shape[0]
            if kind == "state_unchanged":
                new = cache
            elif kind == "token_altered":
                nxt = (nxt + 1) % cfg.vocab
            elif kind == "half_batch":
                nxt = jnp.concatenate([nxt[:b // 2], nxt[:b - b // 2]])
            return nxt, new
        return serve_step
    return make


def one_run(seed):
    bench = {"end_to_end": [{"name": "output_tokens_per_s",
                             "unit": "tokens/s"}], "per_layer": []}
    result, lines = run.run_cell(bench, {"name": "tiny", "chips": 1},
                                 run.load(TINY), MIX, LIMITS, seed, 2.0,
                                 False, None)
    assert lines[-1].startswith("check tokens_compared:")
    assert list(result)[-1] == "checks"
    return result


@pytest.mark.parametrize("seed", [11, 2**31 + 99])
def test_sound_run_is_correct(seed):
    result = one_run(seed)
    assert result["correct"], result["checks"]
    assert result["metrics"]["output_tokens_per_s"]["value"] > 0
    assert result["checks"]["tokens_compared"]["value"] >= 20


@pytest.mark.parametrize("kind", ["state_unchanged", "token_altered",
                                  "half_batch"])
def test_fault_is_caught(kind, monkeypatch):
    import repro.runtime.serve_loop as serve_loop
    monkeypatch.setattr(serve_loop, "make_serve_step", faulty(kind))
    result = one_run(13)
    assert not result["correct"], result["checks"]
    assert result["checks"]["max_logit_gap"]["value"] > 0.5
