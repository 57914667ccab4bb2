"""chip_smoke.py's phases at a tiny size on the CPU.

The script refuses to run without a TPU; its phases are plain functions,
so their control flow and checks run here on a cut-down qwen2.5-3b (4
layers, d_model 256, bf16).  The logits tolerance they enforce is the
one the chip run uses.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    cfg = dataclasses.replace(smoke.get_config(smoke.ARCH), n_layers=4,
                              d_model=256, n_heads=4, n_kv_heads=2,
                              head_dim=64, d_ff=768, vocab=4000)
    return cfg, smoke.init_model(jax.random.PRNGKey(0), cfg)


def test_refuses_cpu_and_prints_no_result(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_serve_phase_completes_every_request(smoke, tiny):
    cfg, params = tiny
    out = smoke.serve_phase(params, cfg, np.random.default_rng(0))
    assert out["requests"] == 8 and out["tokens"] == 8 * 16


def test_logits_checks_hold_within_tolerance(smoke, tiny):
    cfg, params = tiny
    prompt = smoke.prompts(cfg, np.random.default_rng(1), 1)[0]
    pd = smoke.prefill_decode_phase(params, cfg, prompt)
    assert pd["rel_l2"] <= smoke.LOGITS_RTOL
    kern = smoke.kernel_phase(params, cfg, prompt, pd["_prefill"])
    assert kern["rel_l2"] <= smoke.LOGITS_RTOL


def test_plan_request_beside_model_keeps_x64_off(smoke, tiny, monkeypatch):
    """On a TPU backend the planner's auto engine must not import the
    float64 pricer; steer the backend probe and hold x64 off (another
    test in this worker may have turned it on)."""
    cfg, params = tiny
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64(False):
        assert smoke.x64_phase(params, cfg)["engine"] == "numpy"


def test_train_phase_matches_single_device_loss(smoke, tiny):
    cfg, _ = tiny
    out = smoke.train_phase(cfg, seed=0, steps=3, batch=2, seq=32)
    assert out["losses"][-1] < out["losses"][0]
    assert abs(out["losses"][0] - out["single_device_loss"]) \
        <= smoke.LOSS_ATOL


def test_result_line_is_last_and_parses(smoke, monkeypatch, capsys):
    """With a (faked) TPU device and no-op phases, the last stdout line
    is the one JSON object the contract names."""
    class FakeTPU:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 1}

    monkeypatch.setattr(smoke.jax, "devices", lambda: [FakeTPU()])
    monkeypatch.setattr(smoke, "one_chip", lambda cfg, seed: None)
    monkeypatch.setattr(smoke, "place_compile_cache", lambda: "cache")
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
