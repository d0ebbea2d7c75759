"""Compile the main path for a described TPU v5e chip, without the chip.

The TPU compiler is installed alongside JAX, so a program can be compiled
for a v5e that is described rather than attached. This catches what
interpret mode cannot: Mosaic's block-shape and VMEM rules for the Pallas
kernels, and whether a full-width program fits the chip. Nothing runs, so
results and times are not checked here (``chip_smoke.py`` does that on a
chip).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.models import model as M


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (such compiles are written to it but cannot be read back
    without a chip) and the compiler's logs off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache_was = jax.config.jax_enable_compilation_cache
    log_was = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
        if log_was is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_was


# the kernel shapes chip_smoke.py runs (olmo-1b widths), (B, H, T, D) layout
KERNELS = {
    "flash_attention_B1_H16_S2048_D128": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((1, 16, 2048, 128), jnp.bfloat16)] * 3),
    "decode_attention_B8_Hkv16_T4096_D128": (
        decode_attention,
        [((8, 16, 128), jnp.bfloat16), ((8, 16, 4096, 128), jnp.bfloat16),
         ((8, 16, 4096, 128), jnp.bfloat16), ((8,), jnp.int32)]),
    "rmsnorm_4096x2048": (
        rmsnorm, [((4096, 2048), jnp.bfloat16), ((2048,), jnp.bfloat16)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_olmo_1b_decode_step_compiles_for_v5e(one_chip):
    """olmo-1b's decode step at its published widths fits one v5e."""
    cfg = get_config("olmo-1b")
    B, max_len = 1, 40

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree)

    params = on_chip(jax.eval_shape(lambda k: M.init_params(cfg, k),
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: M.init_cache(cfg, B, max_len)))
    token = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, c, t, i: M.decode_step(cfg, p, c, t, i)
                       ).lower(params, cache, token, pos).compile()
    mem = compiled.memory_analysis()
    assert 2 << 30 < mem.argument_size_in_bytes < 16 << 30


def test_moonlight_decode_step_reads_no_whole_bank_on_v5e(one_chip):
    """The Moonlight share's B=1 decode step at its published widths fits
    one v5e, and no value in it is one layer's whole (16, 2048, 1408) bank
    of held experts: each touched expert's matrices are sliced from the
    stacked banks inside its matmul (a bank the layer scan slices is
    copied whole, and read whole by ragged_dot)."""
    import re

    cfg = get_config("moonlight-16b-a3b-ep4")
    B, max_len = 1, 40

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree)

    params = on_chip(jax.eval_shape(lambda k: M.init_params(cfg, k),
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: M.init_cache(cfg, B, max_len)))
    token = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, c, t, i: M.decode_step(cfg, p, c, t, i,
                                                        moe_counts=True)
                       ).lower(params, cache, token, pos).compile()
    n, d, f = cfg.n_held_experts, cfg.d_model, cfg.d_ff
    assert params["layers"]["ffn"]["w_gate"].shape == (26, n, d, f)
    assert not re.search(rf"bf16\[{n},({d},{f}|{f},{d})\]", compiled.as_text())
    assert compiled.memory_analysis().argument_size_in_bytes < 16 << 30
