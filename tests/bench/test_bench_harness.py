"""The harness: it refuses to run without a TPU or without the program,
and finds a new configuration, traffic mix, metric and kind of system by
their names alone (in a copy of the benchmark, with files added and none
edited)."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _bench_only_copy(tmp: Path) -> Path:
    """A directory holding only BENCHMARK.json and the benchmark's paths."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp


def _run(cwd: Path, *args, env=None):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env or _env(), capture_output=True, text=True,
                          timeout=300)


def _no_result(p):
    return not any(line.startswith("{") for line in p.stdout.splitlines())


def test_refuses_a_platform_that_is_not_tpu():
    p = _run(ROOT, "--workload", "qwen2.5-3b.chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == 2 and _no_result(p)
    assert "no TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    """Past the look for a chip, a directory without the program's
    source fails before any result."""
    copy = _bench_only_copy(tmp_path)
    code = ("import sys; sys.path.insert(0, '.'); from bench import run; "
            "b = run.load(run.ROOT / 'BENCHMARK.json'); "
            "wl, c, mix, lim = run.cell(b, 'qwen2.5-3b.chat'); "
            "print(run.run_cell(b, wl, c, mix, lim, 1, 1.0, False, None))")
    p = subprocess.run([sys.executable, "-c", code], cwd=copy, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and _no_result(p)
    assert "No module named 'repro'" in p.stderr


def test_finds_new_config_mix_and_metric_by_name(tmp_path):
    copy = _bench_only_copy(tmp_path)
    tiny = json.loads((ROOT / "tests/bench/fixtures/tiny-dense.json")
                      .read_text())
    (copy / "bench/configs/tiny-new.json").write_text(
        json.dumps({**tiny, "name": "tiny-new"}))
    (copy / "bench/traffic/burst.json").write_text(json.dumps({
        "kind": "serve", "driver": "backlog", "ramp_s": 0.2, "block": 8, "check_requests": 2,
        "prompt": {"median": 8, "sigma": 0.3, "min": 2, "max": 16},
        "output": {"median": 8, "sigma": 0.3, "min": 2, "max": 16}}))
    (copy / "bench/limits/tiny-new.burst.json").write_text(
        json.dumps({"max_logit_gap": 0.1, "min_tokens_compared": 4}))
    (copy / "bench/metrics/ticks_per_s.py").write_text(
        "def read(r):\n"
        "    return len(r.ticks) / (r.ticks[-1, 1] - r.ticks[0, 0])\n")
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-new", "source": "test",
                             "file": "bench/configs/tiny-new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-new.burst", "config": "tiny-new",
                               "traffic": "burst", "chips": 1, "why": "t"})
    bench["end_to_end"].append({"name": "ticks_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["tiny-new.burst"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    code = ("import json, sys; sys.path[:0] = ['.', sys.argv[1]]; "
            "from bench import run; "
            "b = run.load(run.ROOT / 'BENCHMARK.json'); "
            "wl, c, mix, lim = run.cell(b, 'tiny-new.burst'); "
            "res, lines = run.run_cell(b, wl, c, mix, lim, 5, 1.0, False, "
            "None); print(json.dumps(res))")
    p = subprocess.run([sys.executable, "-c", code, str(ROOT / "src")],
                       cwd=copy, env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.splitlines()[-1])
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ticks_per_s", "setup_s"}
    assert res["metrics"]["ticks_per_s"]["value"] > 0
    assert res["metrics"]["ticks_per_s"]["unit"] == "1/s"


# a kind of system that is not a server: a jitted step on a vector, driven
# for the window, checked exactly against the number of steps it ran
TOY_KIND = """
import time
import jax
import jax.numpy as jnp
import numpy as np

STEP = "jit_add_one"


def build(c, mix, seed, devices, phases):
    def add_one(x):
        return x + 1
    step = jax.jit(add_one)
    x = jax.device_put(jnp.full((c["width"],), seed % 1000, jnp.int32),
                       devices[0])
    jax.block_until_ready(step(x))
    phases["warm_up"] = 0.0
    return {"step": step, "x": x, "start": seed % 1000}


def drive(system, c, mix, seed, seconds, tracer):
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() < t0 + seconds:
        system["x"] = system["step"](system["x"])
        n += 1
    jax.block_until_ready(system["x"])
    return {"window": (t0, time.perf_counter()), "attempted": n,
            "failed": 0, "steps": n,
            "final": np.asarray(system["x"]) - system["start"]}


def notes(r):
    return [f"steps in the window: {r.steps}"]


def verify(r, mix, limits, seed):
    err = int(np.abs(r.final - r.steps).max())
    checks = {"max_error": {"value": err, "limit": limits["max_error"]}}
    return checks, err <= limits["max_error"], []
"""


def test_finds_a_new_kind_by_name(tmp_path):
    """A cell whose system is not a server (as a training cell's is not)
    arrives as files only: a kind, a mix that names it, a configuration,
    limits and a metric."""
    copy = _bench_only_copy(tmp_path)
    (copy / "bench/kinds/toy.py").write_text(TOY_KIND)
    (copy / "bench/configs/toy.json").write_text(json.dumps(
        {"name": "toy", "source": "test", "reduced": [], "width": 16}))
    (copy / "bench/traffic/steps.json").write_text(json.dumps(
        {"kind": "toy"}))
    (copy / "bench/limits/toy.steps.json").write_text(json.dumps(
        {"max_error": 0}))
    (copy / "bench/metrics/toy_steps_per_s.py").write_text(
        "def read(r):\n"
        "    return r.steps / (r.window[1] - r.window[0])\n")
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.steps", "config": "toy",
                               "traffic": "steps", "chips": 1, "why": "t"})
    bench["end_to_end"].append({"name": "toy_steps_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["toy.steps"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    code = ("import json, sys; sys.path[:0] = ['.']; "
            "from bench import run; "
            "b = run.load(run.ROOT / 'BENCHMARK.json'); "
            "wl, c, mix, lim = run.cell(b, 'toy.steps'); "
            "res, lines = run.run_cell(b, wl, c, mix, lim, 2**31 + 7, 0.3, "
            "False, None); print('\\n'.join(lines)); print(json.dumps(res))")
    p = subprocess.run([sys.executable, "-c", code], cwd=copy, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.splitlines()[-1])
    assert res["correct"] and res["checks"]["max_error"]["value"] == 0
    assert set(res["metrics"]) == {"toy_steps_per_s", "setup_s"}
    assert res["metrics"]["toy_steps_per_s"]["value"] > 0
    assert res["attempted"] > 0
    assert any(line.startswith("steps in the window:")
               for line in p.stdout.splitlines())
