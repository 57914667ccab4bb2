"""End-to-end behaviour tests: fault-tolerant training, checkpointing,
data determinism, optimizer, sharding rules."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import (AsyncCheckpointer, latest_step,
                                    read_metadata, restore, save)
from repro.configs import SHAPES, decode_input_specs, get_config, input_specs
from repro.data.pipeline import DataConfig, TokenDataset
from repro.launch.mesh import make_host_mesh
from repro.optim.adamw import AdamWConfig, apply_updates, init_state
from repro.runtime.train_loop import FaultInjector, TrainLoopConfig, train


# ---------------------------------------------------------------------------
# training loop + fault tolerance
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_decreases_loss_and_survives_failure():
    cfg = get_config("qwen2.5-3b", smoke=True)
    data = DataConfig(seq_len=64, global_batch=8, vocab=cfg.vocab)
    opt = AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=60)
    with tempfile.TemporaryDirectory() as d:
        loop = TrainLoopConfig(steps=60, ckpt_every=20, ckpt_dir=d,
                               log_every=20)
        out = train(cfg, opt, loop, make_host_mesh, data,
                    fault=FaultInjector(fail_at=30))
        h = out["history"]
        assert out["failures"] == 1
        assert h[-1]["loss"] < h[0]["loss"] * 0.85


@pytest.mark.slow
def test_train_resume_is_seamless():
    """Stopping at step k and restarting produces the same state as a
    straight run (deterministic data + checkpointed opt state)."""
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    data = DataConfig(seq_len=32, global_batch=4, vocab=cfg.vocab)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    with tempfile.TemporaryDirectory() as d1:
        loop = TrainLoopConfig(steps=20, ckpt_every=10, ckpt_dir=d1,
                               log_every=20)
        full = train(cfg, opt, loop, make_host_mesh, data)
    with tempfile.TemporaryDirectory() as d2:
        loop_a = TrainLoopConfig(steps=10, ckpt_every=10, ckpt_dir=d2,
                                 log_every=20)
        train(cfg, opt, loop_a, make_host_mesh, data)
        loop_b = TrainLoopConfig(steps=20, ckpt_every=10, ckpt_dir=d2,
                                 log_every=20)
        resumed = train(cfg, opt, loop_b, make_host_mesh, data)
    a = jax.tree.leaves(full["params"])
    b = jax.tree.leaves(resumed["params"])
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   atol=1e-5, rtol=1e-5)


def test_train_raises_trace_error_without_retry(monkeypatch):
    """A trace-time error is a bug, not a node failure: train() raises it
    on the first attempt instead of re-forming the mesh and retrying."""
    from repro.runtime import train_loop

    def broken_step(*args, **kwargs):
        def step(params, opt_state, batch):
            raise TypeError("bad step")
        return step

    monkeypatch.setattr(train_loop, "make_train_step", broken_step)
    meshes = []

    def mesh_fn():
        meshes.append(make_host_mesh())
        return meshes[-1]

    cfg = get_config("qwen2.5-3b", smoke=True)
    data = DataConfig(seq_len=8, global_batch=2, vocab=cfg.vocab)
    loop = TrainLoopConfig(steps=2, log_every=1)
    with pytest.raises(TypeError, match="bad step"):
        train(cfg, AdamWConfig(), loop, mesh_fn, data)
    assert len(meshes) == 1


def test_compile_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache goes to one fixed, gitignored directory of the checkout."""
    from repro.launch.compile_cache import DEFAULT_DIR, place_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert place_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    root = DEFAULT_DIR.parent
    assert (root / "pyproject.toml").exists()
    assert f"{DEFAULT_DIR.name}/" in (root / ".gitignore").read_text()


# ---------------------------------------------------------------------------
# checkpoint store
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_bf16():
    tree = {"a": jnp.ones((3, 4), jnp.bfloat16) * 1.5,
            "b": {"c": jnp.arange(5, dtype=jnp.int32)},
            "d": jnp.float32(2.5)}
    with tempfile.TemporaryDirectory() as d:
        save(d, 7, tree, {"note": "x"})
        assert latest_step(d) == 7
        assert read_metadata(d, 7)["note"] == "x"
        out = restore(d, 7, tree)
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            assert x.dtype == y.dtype
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32))


def test_checkpoint_atomic_publish():
    tree = {"w": jnp.zeros((2, 2))}
    with tempfile.TemporaryDirectory() as d:
        save(d, 1, tree)
        save(d, 2, tree)
        assert latest_step(d) == 2
        import pathlib
        assert not list(pathlib.Path(d).glob(".tmp_*"))


def test_async_checkpointer():
    tree = {"w": jnp.ones((8, 8))}
    with tempfile.TemporaryDirectory() as d:
        ck = AsyncCheckpointer(d)
        ck.save_async(5, tree)
        ck.wait()
        assert latest_step(d) == 5


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic():
    cfg = DataConfig(seq_len=16, global_batch=4, vocab=100, seed=3)
    a = TokenDataset(cfg).global_batch_at(5)
    b = TokenDataset(cfg).global_batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_data_shards_tile_global_batch():
    cfg = DataConfig(seq_len=16, global_batch=8, vocab=100)
    ds = TokenDataset(cfg)
    full = ds.global_batch_at(2)["tokens"]
    parts = [ds.shard_batch_at(2, s, 4)["tokens"] for s in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), full)


def test_labels_are_shifted_tokens():
    cfg = DataConfig(seq_len=16, global_batch=2, vocab=100)
    b = TokenDataset(cfg).global_batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_descends_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=1, total_steps=100,
                      weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = init_state(params)
    for _ in range(50):
        grads = {"w": 2 * params["w"]}
        params, state = apply_updates(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 1.0


@pytest.mark.parametrize("scale", [0.1, 0.7, 1.0, 3.3, 10.0])
def test_adamw_clips_gradients(scale):
    cfg = AdamWConfig(lr=1e-3, clip_norm=1.0, warmup_steps=1)
    params = {"w": jnp.zeros((4,))}
    state = init_state(params)
    grads = {"w": jnp.full((4,), scale * 100.0)}
    p2, _ = apply_updates(cfg, params, grads, state)
    assert np.isfinite(np.asarray(p2["w"])).all()
    # clipped update magnitude bounded by ~lr regardless of grad scale
    assert float(jnp.abs(p2["w"]).max()) < 10 * cfg.lr


# ---------------------------------------------------------------------------
# sharding rules (logical level — lowering covered by the dry-run)
# ---------------------------------------------------------------------------

def test_input_specs_cover_all_cells():
    for arch in ("qwen2.5-3b", "whisper-medium", "qwen2-vl-2b",
                 "rwkv6-1.6b"):
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if shape.kind == "decode":
                sp = decode_input_specs(cfg, shape)
                assert sp["tokens"].shape == (shape.global_batch, 1)
                assert "cache" in sp
            else:
                sp = input_specs(cfg, shape)
                assert sp["tokens"].shape == (shape.global_batch,
                                              shape.seq_len)


def test_hint_noop_without_mesh():
    from repro.distributed.hints import hint
    x = jnp.ones((4, 4))
    np.testing.assert_array_equal(hint(x, "batch", "model"), x)


def test_param_spec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import param_spec

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    m = FakeMesh()
    # wq (scanned): (L, D, H) -> (None, data, model)
    sp = param_spec(("layers", "attn", "wq"), (4, 2048, 2048), m, True)
    assert sp == P(None, "data", "model")
    # moe experts: (L, E, D, F) -> expert-parallel + FSDP over data
    sp = param_spec(("layers", "moe", "w_gate"), (4, 32, 1024, 512), m, True)
    assert sp == P(None, "model", "data", None)
    # embed: vocab over model when divisible
    sp = param_spec(("embed",), (49408, 1024), m, False)
    assert sp == P("model", None)
    # odd vocab stays replicated
    sp = param_spec(("embed",), (49155, 1024), m, False)
    assert sp == P(None, None)
