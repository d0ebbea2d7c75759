#!/usr/bin/env python3
"""Bring-up smoke test: the TrIMS main path on one TPU chip, at full width.

    python chip_smoke.py [--seed N]

Run from the repository root, as one process (it must be the only process
using the chip). It refuses to run when JAX finds no TPU; there is no CPU
fallback. Phases:

1. kernels — each Pallas kernel compiled for the chip (``interpret=False``)
   at olmo-1b widths, checked against its ``kernels/ref.py`` oracle
   evaluated in float32:
   flash attention B1 H16 S2048 D128 causal; decode attention B8 Hkv16
   T4096 D128 over a mix of ``kv_len``; rmsnorm 4096x2048.
2. model — ``olmo-1b`` at its published config (16 layers, d_model 2048,
   vocab 50304; 1.18 B params, seeded random weights) published to a fresh
   store in a temporary directory and served by ``repro.launch.serve``:
   a cold open from the disk tier, a second request that must hit the
   device tier, and one request through ``InferenceEngine(use_trims=False)``.
   All three must give identical tokens, and the chip's last-position
   prefill logits must match a float32 run of the same stored weights on
   this process's CPU device.

Readings printed on the way (compile seconds, wall seconds, peak device
bytes) are bring-up readings, not benchmark results; ``modeled_*`` values
come from the ``HardwareModel`` datasheet constants, not from the chip.
The last line of standard output is one JSON object, printed only when
every phase passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "olmo-1b"
# Kernels take bf16 inputs and write bf16 outputs, so they are held to the
# bf16 tolerance of the repo's kernel tests: |got - want| <= ATOL + RTOL*|want|.
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2
# Relative L2 error of the chip's bf16 prefill logits against float32 on the
# CPU. bf16 against float32 on the CPU, same seeded olmo-1b weights, gives
# 1.0e-2 at 2 layers and 1.1e-2 at 4 (full width); the limit leaves room for
# the growth to 16 layers and for the chip's own bf16 rounding order.
LOGITS_REL_L2_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _kernel_check(name, fn, args, want) -> bool:
    got, first_s = _timed(fn, *args)
    _, again_s = _timed(fn, *args)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    ratio = float(np.max(err / (KERNEL_ATOL + KERNEL_RTOL * np.abs(want))))
    ok = bool(np.isfinite(got).all()) and got.shape == want.shape and ratio <= 1.0
    log(f"kernel {name}: shape={got.shape} max_abs_err={float(err.max())!r} "
        f"tol_ratio={ratio!r} (<= 1 passes) first_call_s={first_s!r} "
        f"second_call_s={again_s!r} {'ok' if ok else 'FAIL'}")
    return ok


def kernel_phase(seed: int) -> bool:
    from repro.kernels import ops, ref

    keys = jax.random.split(jax.random.PRNGKey(seed), 8)

    def rand(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)

    def heads_first(x):  # (B, T, H, D) bf16 -> (B, H, T, D) float32
        return jnp.swapaxes(x.astype(jnp.float32), 1, 2)

    def oracle(fn, *args):  # the reference, with float32 matmuls
        with jax.default_matmul_precision("float32"):
            return jax.block_until_ready(fn(*args))

    # flash attention, (B, S, H, D) model layout
    q, k, v = (rand(keys[i], (1, 2048, 16, 128)) for i in range(3))
    want = oracle(lambda: jnp.swapaxes(ref.mha_reference(
        heads_first(q), heads_first(k), heads_first(v), causal=True), 1, 2))
    ok = _kernel_check(
        "flash_attention B1 H16 S2048 D128 causal",
        jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True)),
        (q, k, v), want)

    # decode attention over a (B, T, Hkv, D) cache, kv_len across blocks
    qd = rand(keys[3], (8, 16, 128))
    kc, vc = (rand(keys[i], (8, 4096, 16, 128)) for i in (4, 5))
    kv_len = jnp.asarray([1, 100, 255, 256, 257, 1000, 2049, 4096], jnp.int32)
    want = oracle(lambda: ref.decode_reference(
        qd.astype(jnp.float32), heads_first(kc), heads_first(vc), kv_len))
    ok &= _kernel_check(
        f"decode_attention B8 Hkv16 T4096 D128 kv_len={kv_len.tolist()}",
        jax.jit(ops.decode_attention), (qd, kc, vc, kv_len), want)

    x = rand(keys[6], (4096, 2048))
    scale = rand(keys[7], (2048,)) + 1
    want = oracle(lambda: ref.rmsnorm_reference(x.astype(jnp.float32),
                                                scale.astype(jnp.float32)))
    ok &= _kernel_check("rmsnorm 4096x2048", jax.jit(ops.rmsnorm), (x, scale),
                        want)
    return ok


def reference_logits(cfg, flat, prompt, max_len, device, dtype="float32"):
    """Last-position prefill logits of the stored weights ``flat`` computed
    on ``device`` with params and activations in ``dtype``."""
    from repro.models import init_params, prefill
    from repro.serving.weights_io import flat_to_params_like

    cfg = cfg.replace(param_dtype=dtype, compute_dtype=dtype)
    with jax.default_device(device):
        template = jax.eval_shape(lambda k: init_params(cfg, k),
                                  jax.random.PRNGKey(0))
        params = flat_to_params_like(
            template, flat,
            convert=lambda a: jax.device_put(np.asarray(a, dtype), device))
        tokens = jax.device_put(np.asarray(prompt, np.int32), device)
        logits, _ = jax.jit(lambda p, t: prefill(cfg, p, {"tokens": t},
                                                 max_len))(params, tokens)
        return np.asarray(logits, np.float32)


def rel_l2(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def model_phase(seed: int) -> bool:
    from repro.core.mrm import ModelKey
    from repro.launch.serve import serve_once, serving_config
    from repro.serving import FRAMEWORK

    ok = True
    max_new = 8
    with tempfile.TemporaryDirectory(prefix="trims_chip_smoke_") as tmp:
        t0 = time.perf_counter()
        r = serve_once(ARCH, reduced=False, store_dir=f"{tmp}/models",
                       requests=2, baseline=True, max_new=max_new, seed=seed)
        cfg, engine, prompt = r["cfg"], r["engine"], r["prompt"]
        log(f"model {ARCH}: serve_once wall_s={time.perf_counter() - t0!r} "
            f"(publish + 2 TrIMS requests + 1 baseline request)")
        wide = cfg == serving_config(ARCH, reduced=False)
        mf = engine.disk.open(ModelKey(FRAMEWORK, ARCH, "1"))
        log(f"model config: n_layers={cfg.n_layers} d_model={cfg.d_model} "
            f"n_heads={cfg.n_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
            f"params={cfg.param_count()} stored_bytes={mf.total_bytes} "
            f"published_widths={'ok' if wide else 'FAIL'}")
        ok &= wide

        runs = [(f"trims req{i}", out, st)
                for i, (out, st) in enumerate(zip(r["outputs"], r["stats"]))]
        runs.append(("no-trims", *r["baseline"]))
        for label, out, st in runs:
            log(f"request {label}: tier={st.tier_hit} "
                f"load_s={st.model_load_s!r} compile_s={st.compile_s!r} "
                f"compute_s={st.compute_s!r} ttft_s={st.ttft_s!r} "
                f"wall_s={st.total_s!r} modeled_load_s={st.modeled_load_s!r} "
                f"tokens={out[0].tolist()}")
        tiers = [st.tier_hit for _, _, st in runs[:2]]
        tiers_ok = tiers == ["disk", "device"]
        log(f"tiers: {tiers} (want ['disk', 'device']) "
            f"{'ok' if tiers_ok else 'FAIL'}")
        same = all(np.array_equal(runs[0][1], out) for _, out, _ in runs[1:])
        log(f"tokens identical across cold, device-hit and no-trims: "
            f"{'ok' if same else 'FAIL'}")
        ok &= tiers_ok and same

        # the vocab pad columns hold a -1e30 mask, not logits: compare the rest
        V = cfg.vocab_size
        chip = engine.prefill_logits(ARCH, prompt, max_new)[:, :V]
        t0 = time.perf_counter()
        want = reference_logits(cfg, mf.read_all(), prompt,
                                prompt.shape[1] + max_new,
                                jax.devices("cpu")[0])[:, :V]
        err = rel_l2(chip, want)
        close = bool(np.isfinite(chip).all()) and err <= LOGITS_REL_L2_TOL
        log(f"prefill logits {chip.shape}: rel_l2_vs_cpu_f32={err!r} "
            f"(<= {LOGITS_REL_L2_TOL}) argmax_agree="
            f"{bool((chip.argmax(-1) == want.argmax(-1)).all())} "
            f"cpu_reference_s={time.perf_counter() - t0!r} "
            f"{'ok' if close else 'FAIL'}")
        ok &= close
        log(f"mrm: {r['mrm_stats']['disk_loads']} disk loads, "
            f"device hits {r['mrm_stats']['device']['hits']}")
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind}); not running",
              file=sys.stderr)
        return 2
    from repro.launch.serve import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} count={len(jax.devices())} "
        f"jax={jax.__version__}")
    log(f"compile cache: {enable_compile_cache()}")

    failed = []
    for name, phase in (("kernels", lambda: kernel_phase(args.seed)),
                        ("model", lambda: model_phase(args.seed))):
        t0 = time.perf_counter()
        try:
            passed = phase()
        except Exception:  # noqa: BLE001 — report, run the next phase, fail
            traceback.print_exc()
            passed = False
        log(f"phase {name}: {'ok' if passed else 'FAIL'} "
            f"wall_s={time.perf_counter() - t0!r}")
        if not passed:
            failed.append(name)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
