"""The benchmark's token stream and weights are functions of the seed."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import data, weights  # noqa: E402

TOKENS = {"marginal_power": 2.5, "mean_doc_len": 512, "eos_id": 0}
BIG = 2 ** 31 + 12345


def batch(seed, step):
    return data.round_batch(seed, step, microbatches=8, rows=1,
                            seq_len=4096, vocab=32000, **TOKENS)


@pytest.mark.parametrize("seed", [0, 7, BIG, 2 ** 40 + 3])
def test_same_seed_same_rows(seed):
    a, b = batch(seed, 3), batch(seed, 3)
    assert a["tokens"].shape == (8, 1, 4096)
    assert a["tokens"].dtype == np.int32
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["labels"], b["labels"])
    # labels are the next tokens of the same draw
    np.testing.assert_array_equal(a["tokens"][..., 1:], a["labels"][..., :-1])


def test_rows_differ_across_seeds_steps_and_microbatches():
    a, b, c = batch(BIG, 0), batch(BIG + 1, 0), batch(BIG, 1)
    assert (a["tokens"] != b["tokens"]).mean() > 0.5
    assert (a["tokens"] != c["tokens"]).mean() > 0.5
    rows = a["tokens"].reshape(8, -1)
    assert len({r.tobytes() for r in rows}) == 8


def test_marginal_and_documents():
    t = batch(11, 0)["tokens"]
    assert t.min() >= 0 and t.max() < 32000
    # u ** 2.5 puts half the tokens below 32000 * 0.5 ** 2.5 ~ 5657
    assert np.median(t) == pytest.approx(32000 * 0.5 ** 2.5, rel=0.05)
    # id 0 is both the end of a document (1 in 512) and every draw below
    # 1 / 32000 of the scale (u < 32000 ** -0.4, about 1.6%)
    zero = (t == 0).mean()
    assert zero == pytest.approx(1 / 512 + 32000 ** -0.4, rel=0.1)


def test_weights_follow_the_seed():
    import jax
    import jax.numpy as jnp
    with open(os.path.join(os.path.dirname(__file__),
                           "tiny-danube.json")) as f:
        cfg = json.load(f)
    like = {"embed": jax.ShapeDtypeStruct((16, 8), jnp.bfloat16),
            "head": jax.ShapeDtypeStruct((8, 16), jnp.bfloat16),
            "final_norm": {"scale": jax.ShapeDtypeStruct((8,), jnp.bfloat16)},
            "layer_thetas": jnp.full((1, 2), 7.0)}
    a = weights.generate(cfg, weights.seed_key(BIG), like)
    b = weights.generate(cfg, weights.seed_key(BIG), like)
    c = weights.generate(cfg, weights.seed_key(BIG + 2 ** 32), like)
    assert a["embed"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])
    np.testing.assert_array_equal(a["final_norm"]["scale"], 1.0)
    np.testing.assert_array_equal(a["layer_thetas"], 7.0)   # kept
    with pytest.raises(KeyError):
        weights.generate(cfg, weights.seed_key(1),
                         {"mystery": jax.ShapeDtypeStruct((2,), jnp.float32)})
