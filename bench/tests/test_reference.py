"""The reference follows the architecture the program serves: at a small
size, in float32, its logits match the program's own forward pass on the
same seeded weights (the program's model code is used here only as a
witness; the reference imports none of it). The dense decoder's logits are
bit for bit those recorded before the architecture seam."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import weights as W
from bench.arch import dense
from bench.tests import tiny

GOLDEN = json.loads((Path(__file__).resolve().parent / "data"
                     / "dense_golden.json").read_text())


def _program_logits(arch, model, seed, tokens):
    from repro.launch.serve import serving_config
    from repro.models import model as M

    cfg = serving_config(arch, reduced=True).replace(
        param_dtype="float32", compute_dtype="float32")
    flat = {k: v.astype(jnp.float32) for k, v in W.make_flat(
        seed, dense.weight_groups(model, cfg.padded_vocab)).items()}
    params = dense.nest(flat, model)
    with jax.default_matmul_precision("highest"):
        logits, _ = M.forward(cfg, params, {"tokens": jnp.asarray(tokens)[None]})
    return np.asarray(logits[0, :, : model["vocab_size"]])


@pytest.mark.parametrize("arch,norm", [("olmo-1b", "layernorm_nonparametric"),
                                       ("deepseek-7b", "rmsnorm")])
def test_reference_matches_program_forward(arch, norm):
    model = dict(tiny.CONFIG["model"], norm=norm)
    seed = 2 ** 32 + 99
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    want = _program_logits(arch, model, seed, tokens)
    got = dense.logits_at(seed, model, 256, [tokens], [np.arange(40)])[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_served_gaps_zero_for_reference_tokens_and_control_runs():
    model = tiny.CONFIG["model"]
    prompt = np.random.default_rng(1).integers(0, 256, 24).astype(np.int32)
    # greedy continuation by the reference itself: every gap is 0
    seq = list(prompt)
    for _ in range(6):
        lg = dense.logits_at(5, model, 256, [np.array(seq, np.int32)],
                             [np.array([len(seq) - 1])])[0]
        seq.append(int(lg[0].argmax()))
    served = np.array(seq[len(prompt):], np.int32)
    r = harness.served_gaps(dense, 5, model, 256, [prompt], [served],
                            control=True)
    assert float(r["gap"][0].max()) < 1e-5
    assert r["control_gap"][0].shape == (6,)
    assert float(r["control_gap"][0].min()) >= 0.0


@pytest.mark.parametrize("norm", ["layernorm_nonparametric", "rmsnorm"])
def test_logits_as_recorded(norm):
    model = dict(tiny.CONFIG["model"], norm=norm)
    n = GOLDEN["prompt_len"]
    tokens = np.random.default_rng(0).integers(0, 256, n).astype(np.int32)
    got = dense.logits_at(GOLDEN["logit_seed"], model, 256, [tokens],
                          [np.arange(n)])[0]
    got = np.ascontiguousarray(got, np.float32)
    want = GOLDEN["logits"][norm]
    assert list(got.shape) == want["shape"]
    assert hashlib.sha256(got.tobytes()).hexdigest() == want["sha256"]
