"""Seeded weights: the one-call set and the reference's layer-at-a-time
draws agree bit for bit, the fingerprint sees a one-element change, and the
dense decoder's weights are those recorded before the architecture seam."""
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights as W
from bench.arch import dense
from bench.tests import tiny

RMS = dict(tiny.CONFIG["model"], norm="rmsnorm")
GOLDEN = json.loads((Path(__file__).resolve().parent / "data"
                     / "dense_golden.json").read_text())


def _layout(model):
    return dense.weight_groups(model, 256)


@pytest.mark.parametrize("model", [tiny.CONFIG["model"], RMS],
                         ids=["layernorm", "rmsnorm"])
def test_layers_and_stem_drawn_alike(model):
    seed = 2 ** 31 + 12345
    stem, groups = _layout(model)
    flat = W.make_flat(seed, (stem, groups))
    assert all(v.dtype == jnp.bfloat16 for v in flat.values())
    (group,) = groups
    for i in range(model["n_layers"]):
        one = W.layer_f32(seed, group, i)
        for name, v in one.items():
            np.testing.assert_array_equal(
                np.asarray(flat["layers/" + name][i], np.float32), np.asarray(v))
    for name, v in W.stem_f32(seed, stem).items():
        np.testing.assert_array_equal(np.asarray(flat[name], np.float32),
                                      np.asarray(v))
    has_scales = "layers/ln1/scale" in flat
    assert has_scales == (model["norm"] == "rmsnorm")


def test_seeds_past_32_bits_differ():
    layout = _layout(tiny.CONFIG["model"])
    a = W.make_flat(2 ** 40 + 5, layout)["embed"]
    b = W.make_flat(5, layout)["embed"]
    c = W.make_flat(2 ** 40 + 5, layout)["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError):
        W.base_key(-1)


def test_fingerprint_sees_one_element():
    flat = W.make_flat(3, _layout(tiny.CONFIG["model"]))
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
    tree = dense.nest(W.make_flat(1, _layout(m)), m)
    assert tree["final_norm"] == {} and tree["layers"]["ln1"] == {}
    assert set(tree["layers"]["attn"]) == {"wq", "wk", "wv", "wo"}


@pytest.mark.parametrize("seed", [7, 2 ** 33 + 7])
@pytest.mark.parametrize("norm", ["layernorm_nonparametric", "rmsnorm"])
def test_fingerprints_as_recorded(norm, seed):
    model = dict(tiny.CONFIG["model"], norm=norm)
    got = W.fingerprints(W.make_flat(seed, _layout(model)))
    want = {k: tuple(v) for k, v in GOLDEN["weights"][norm][str(seed)].items()}
    assert got == want
