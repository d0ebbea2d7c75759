"""Per-architecture smoke tests: reduced config, one forward + one train step
on CPU, asserting output shapes and absence of NaNs; plus a prefill/decode
consistency check per family.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # skipped by scripts/ci.sh --fast

from repro.configs import ARCHS, get_config, list_archs
from repro.data.pipeline import make_batch
from repro.models import (decode_step, forward, init_params, loss_fn, prefill)
from repro.optim import adamw, constant

B, S = 2, 64


def _setup(arch):
    cfg = get_config(arch).reduced()
    if cfg.n_experts:
        cfg = cfg.replace(moe_impl="ragged")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, 0, B, S)
    return cfg, params, batch


@pytest.mark.parametrize("arch", list_archs())
def test_forward_shapes_no_nan(arch):
    cfg, params, batch = _setup(arch)
    logits, aux = jax.jit(lambda p, b: forward(cfg, p, b))(params, batch)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", list_archs())
def test_train_step_no_nan(arch):
    cfg, params, batch = _setup(arch)
    init, update = adamw(constant(1e-3))
    opt = init(params)

    @jax.jit
    def step(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
        new_params, opt, om = update(grads, opt, params)
        return new_params, opt, loss, metrics

    p1, opt, loss, metrics = step(params, opt, batch)
    assert np.isfinite(float(loss))
    # loss must be near ln(V) at init for hash-random tokens
    assert float(loss) < np.log(cfg.vocab_size) * 2.5
    # parameters actually changed (embedding always receives gradient)
    assert not np.allclose(np.asarray(params["embed"], np.float32),
                           np.asarray(p1["embed"], np.float32))
    for leaf in jax.tree.leaves(p1):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


@pytest.mark.parametrize("arch", list_archs())
def test_prefill_decode_consistency(arch):
    """logits from (prefill prompt; decode token t) must match the full
    forward pass at position t — the KV-cache/recurrent-state path is exact."""
    cfg, params, batch = _setup(arch)
    max_len = S + 8

    full_logits, _ = forward(cfg, params, batch)
    pf_logits, cache = prefill(cfg, params, batch, max_len)
    np.testing.assert_allclose(
        np.asarray(pf_logits, np.float32),
        np.asarray(full_logits[:, -1], np.float32), rtol=2e-2, atol=2e-2)

    # one decode step == forward at position S of the extended sequence
    next_tok = batch["tokens"][:, -1]  # arbitrary token to feed
    ext = dict(batch)
    ext["tokens"] = jnp.concatenate([batch["tokens"], next_tok[:, None]], axis=1)
    dec_logits, _ = decode_step(cfg, params, cache, next_tok, jnp.int32(S))
    # reference: full forward over S+1 tokens (chunking may fall back to dense)
    ref_logits, _ = forward(cfg, params, ext)
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(ref_logits[:, -1], np.float32), rtol=5e-2, atol=5e-2)


def test_param_count_matches_init():
    for arch in list_archs():
        cfg = get_config(arch).reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        est = cfg.param_count()
        assert abs(actual - est) / actual < 0.35, (
            f"{arch}: analytic {est} vs actual {actual}")


def test_full_config_param_counts_sane():
    """Full (non-reduced) configs land near their advertised sizes."""
    expect = {
        # the published total, 15.96B: MLA, 1 dense + 26 MoE layers of
        # 64 experts of 1408, 2 shared, untied head
        "moonlight-16b-a3b": (15.5e9, 16.5e9),
        "qwen3-moe-30b-a3b": (24e9, 36e9),
        "llama-3.2-vision-90b": (70e9, 105e9),
        "mistral-nemo-12b": (10e9, 14.5e9),
        "deepseek-7b": (5.5e9, 8e9),
        "olmo-1b": (0.8e9, 1.6e9),
        "qwen1.5-110b": (95e9, 125e9),
        "jamba-1.5-large-398b": (330e9, 440e9),
        "mamba2-370m": (0.25e9, 0.5e9),
        "seamless-m4t-large-v2": (0.8e9, 2.5e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B params outside [{lo/1e9},{hi/1e9}]B"
