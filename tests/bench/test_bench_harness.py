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


# in a copy: greedy tokens from the stacked fixture's own reference, then
# the check of ``correct`` on them, and on them with the index ignored
STACKED_RUN = """
import json, sys
sys.path[:0] = ['.']
import jax
import numpy as np
from bench.lib import check, weights

c = json.load(open('bench/configs/tiny-stacked.json'))
seed = 2**31 + 11
lay, ref = check._layout_and_reference(c)
tree = weights.make_tree(lay, weights.stacks(c))(*weights.seed_words(seed))
rng = np.random.default_rng(seed)
seqs = [(rng.integers(0, c['vocab_size'], n).tolist(), []) for n in (30, 9, 41)]
with jax.default_matmul_precision('highest'):
    head = check._head_fn('f32', ref.matmul)
    for _ in range(8):
        fed, _, _ = check._pack([(p, o + [0]) for p, o in seqs],
                                c['serve']['max_len'])
        xs, w = check._hidden(c, lay, ref, seed, fed, 'f32')
        arg = np.concatenate([np.asarray(head(x, w, np.zeros(x.shape[:2],
                                                             np.int32))[2])
                              for x in xs])
        for i, (p, o) in enumerate(seqs):
            o.append(int(arg[i, len(p) + len(o) - 1]))
gaps = check.logit_gaps(c, seed, seqs)['f32']
layer = ref.layer
ref.layer = lambda p, x, c, mode: layer(p, x, c, mode, index=0)
blind = check.logit_gaps(c, seed, seqs)['f32']
print(json.dumps({
    'lengths': {k: v.shape[0] for k, v in
                [('dense_layers', tree['dense_layers']['ln1']),
                 ('layers', tree['layers']['ln1'])]},
    'unembed': list(tree['unembed'].shape),
    'order': check._order(c, ref), 'gaps': gaps, 'blind': blind}))
"""


def test_finds_a_stacked_untied_kind_by_name(tmp_path):
    """A configuration whose layers differ (a 1-layer dense stack before a
    3-layer expert stack, a window on every other layer by its index, an
    untied head) arrives as files only: a layout, a reference and a
    configuration; no file under ``bench/lib`` is edited."""
    copy = _bench_only_copy(tmp_path)
    fix = ROOT / "tests/bench/fixtures"
    shutil.copy(fix / "weights_stacked.py", copy / "bench/weights/stacked.py")
    shutil.copy(fix / "reference_layered.py",
                copy / "bench/reference/stacked.py")
    shutil.copy(fix / "tiny-stacked.json",
                copy / "bench/configs/tiny-stacked.json")
    p = subprocess.run([sys.executable, "-c", STACKED_RUN], cwd=copy,
                       env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.splitlines()[-1])
    assert res["lengths"] == {"dense_layers": 1, "layers": 3}
    assert res["unembed"] == [64, 512]
    assert res["order"] == [["dense_layers", 0], ["layers", 0],
                            ["layers", 1], ["layers", 2]]
    assert res["gaps"]["positions"] == 3 * 8
    assert res["gaps"]["max_gap"] == 0.0, res["gaps"]
    # the index ignored (every layer local) reads 2.78 on the CPU
    assert res["blind"]["max_gap"] > 1.0, res["blind"]
    for f in (ROOT / "bench/lib").glob("*.py"):
        assert (copy / "bench/lib" / f.name).read_bytes() == f.read_bytes()
