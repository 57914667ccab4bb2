"""``bench/counts`` against hand-worked values and the program's tree."""
import json
from pathlib import Path

import jax
import pytest

from bench.counts import dense, moe

ROOT = Path(__file__).resolve().parents[2]


def cfg(name):
    return json.loads((ROOT / "bench/configs" / f"{name}.json").read_text())


QWEN, GRANITE = cfg("qwen2.5-3b"), cfg("granite-moe-1b-a400m")


def test_parameter_counts_by_hand():
    # qwen2.5-3b: per layer q/k/v/o 9,437,184 + bias 2,560 + MLP
    # 67,633,152 + norms 4,096 = 77,076,992; x36 + 152,064 x 2048 + 2048
    assert dense.params(QWEN) == 3_086_200_832
    # granite: attention 3,145,728 + router 32,768 + experts 50,331,648
    # + norms 2,048 = 53,512,192; x24 + 49,408 x 1024 + 1024
    assert moe.params(GRANITE) == 1_334_887_424


@pytest.mark.parametrize("name,counts", [("qwen2.5-3b", dense),
                                         ("granite-moe-1b-a400m", moe)])
def test_parameter_counts_match_the_program(name, counts):
    from repro.configs import get_config
    from repro.models import init_model
    shapes = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                               get_config(name)))
    assert counts.params(cfg(name)) == sum(
        x.size for x in jax.tree.leaves(shapes))


def test_untied_head_is_counted_like_the_programs():
    import dataclasses
    from repro.configs import get_config
    from repro.models import init_model
    c = json.loads((ROOT / "tests/bench/fixtures/tiny-untied.json")
                   .read_text())
    prog = dataclasses.replace(get_config(c["arch"], smoke=True),
                               tie_embeddings=False)
    shapes = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), prog))
    assert dense.params(c) == sum(x.size for x in jax.tree.leaves(shapes))
    # the head is one matmul over the real vocabulary, tied or not
    assert dense.decode_flops(c, 1, 0) == dense.decode_flops(
        {**c, "tie_word_embeddings": True}, 1, 0)


def test_flops_per_token_by_hand():
    # one token, no keys: 2 x (36 x (9,439,744 + 67,633,152)
    #                          + 151,936 x 2048)
    assert dense.decode_flops(QWEN, 1, 0) == 2 * (
        36 * (9_439_744 + 67_633_152) + 151_936 * 2048) == 6_171_578_368
    # attention: 4 x 36 layers x 16 heads x 128 x keys
    assert dense.attention_flops(QWEN, 1000) == 294_912_000
    # granite: top-8 of 32 experts, 1,572,864 each; router 32,768
    per_layer = 3_145_728 + 32_768 + 8 * 1_572_864
    assert moe.decode_flops(GRANITE, 1, 0) == 2 * (
        24 * per_layer + 49_155 * 1024) == 857_217_024


def test_bytes_per_step():
    # 32 live slots read the weights once, bf16, and their KV rows:
    # 2 (k, v) x 36 layers x 2 heads x 128 x 2 bytes = 36,864 per row
    assert dense.kv_row_bytes(QWEN) == 36_864
    w = 2 * (36 * (9_439_744 + 67_633_152) + 151_936 * 2048)
    assert dense.decode_bytes(QWEN, 32, 1000) == w + 36_864 * 1032
    assert dense.decode_bytes(QWEN, 0, 0) == 0
    # with 32 slots, 32 x (1 - 0.75 ** 32) = 31.9968 experts are read
    assert moe.experts_read(GRANITE, 32) == pytest.approx(31.9968, abs=1e-4)
    assert moe.experts_read(GRANITE, 1) == pytest.approx(8)
