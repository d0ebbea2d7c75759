"""Seeded weights: the one-call set and the reference's layer-at-a-time
draws agree bit for bit, and the fingerprint sees a one-element change."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights as W
from bench.tests import tiny

RMS = dict(tiny.CONFIG["model"], norm="rmsnorm")


@pytest.mark.parametrize("model", [tiny.CONFIG["model"], RMS],
                         ids=["layernorm", "rmsnorm"])
def test_layers_and_stem_drawn_alike(model):
    seed = 2 ** 31 + 12345
    flat = W.make_flat(seed, model, 256)
    assert all(v.dtype == jnp.bfloat16 for v in flat.values())
    for i in range(model["n_layers"]):
        one = W.layer_f32(seed, model, i)
        for name, v in one.items():
            np.testing.assert_array_equal(
                np.asarray(flat["layers/" + name][i], np.float32), np.asarray(v))
    for name, v in W.stem_f32(seed, model, 256).items():
        np.testing.assert_array_equal(np.asarray(flat[name], np.float32),
                                      np.asarray(v))
    has_scales = "layers/ln1/scale" in flat
    assert has_scales == (model["norm"] == "rmsnorm")


def test_seeds_past_32_bits_differ():
    m = tiny.CONFIG["model"]
    a = W.make_flat(2 ** 40 + 5, m, 256)["embed"]
    b = W.make_flat(5, m, 256)["embed"]
    c = W.make_flat(2 ** 40 + 5, m, 256)["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError):
        W.base_key(-1)


def test_fingerprint_sees_one_element():
    flat = W.make_flat(3, tiny.CONFIG["model"], 256)
    want = W.fingerprints(flat)
    host = {k: np.asarray(v) for k, v in flat.items()}
    assert W.fingerprints(host) == want
    bad = dict(host)
    w = bad["layers/attn/wq"].copy()
    w[1, 5, 7] = -w[1, 5, 7] if w[1, 5, 7] != 0 else 1
    bad["layers/attn/wq"] = w
    got = W.fingerprints(bad)
    assert got["layers/attn/wq"] != want["layers/attn/wq"]
    assert {k for k in want if got[k] != want[k]} == {"layers/attn/wq"}
    swapped = dict(host, **{"layers/attn/wk": host["layers/attn/wv"],
                            "layers/attn/wv": host["layers/attn/wk"]})
    got = W.fingerprints(swapped)
    assert got["layers/attn/wk"] != want["layers/attn/wk"]


def test_nest_keeps_parameter_free_norms():
    m = tiny.CONFIG["model"]
    tree = W.nest(W.make_flat(1, m, 256), m)
    assert tree["final_norm"] == {} and tree["layers"]["ln1"] == {}
    assert set(tree["layers"]["attn"]) == {"wq", "wk", "wv", "wo"}
