#!/usr/bin/env python3
"""Readings behind an ``mla_moe`` configuration's ``logit_gap`` limit.

    python3 bench/limits_mla_moe.py --workload moonlight-16b-a3b.shared-4fn \\
        --seeds 1,2,3 [--down-scale 1.0] [--routes] [--serve] [--control] \\
        [--faults drop,roll]

Runs on the chip, on the seeded weights and the jitted ``prefill`` and
``decode_step`` the engine runs, without a window. One JSON line a seed on
standard output:

- with ``--routes``, ``free_differ``: per MoE layer, the share of tokens of
  the longest sampled sequence whose top-k set differs between the program
  (bfloat16, layer by layer from its own input) and the float32 reference.
- ``forced_differ``: the same with each program layer fed the reference's
  input, so that only that layer's rounding separates them; what rounding
  alone predicts for the first MoE layer, whose input differs only by the
  rounding of the layers before it.
- ``forced_err``: per layer, the median over the tokens whose sets agree of
  |program update - reference update| / |reference update|, an update being
  what the layer adds to the residual stream. A fault of one layer on the
  chip (the held experts' grouped matmuls, say) shows here whatever the
  layers before it did.
- with ``--serve``: ``logit_gap`` of the sampled requests as ``bench/run.py``
  reads it; with ``--control`` the float8 control's; with ``--faults`` the gap
  of the same requests served with a fault planted in the routed path:
  ``drop`` (the routed experts' output left out: their down projection
  zeroed) and ``roll`` (each held expert's down projection taken from the
  next held expert).

The sample is drawn from the first 32 requests of the closed loop by the
harness's rule (the longest and seven more), where ``bench/run.py`` draws it
from the requests its window finished. ``--down-scale`` replaces the routed
down projection's assumed std factor (``model.routed_down_scale``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SPECS = 32


def _program_layer(cfg, moe: bool):
    """(layer, x (1, T, d), positions) -> (x', routes (T, k) or None)."""
    import jax

    from repro.models import layers as L
    from repro.models import mla as MLA
    from repro.models import model as PM
    from repro.models import moe as MOE

    def f(lay, x, pos):
        routes = None
        if moe:
            h = L.apply_norm(cfg, lay["ln1"], x)
            x1 = x + MLA.prefill(cfg, lay["attn"], h, pos)[0]
            h2 = L.apply_norm(cfg, lay["ln2"], x1)
            routes = MOE.router_topk(cfg, lay["ffn"], h2.reshape(-1, h2.shape[-1]))[1]
        cl = PM._attn_cache_zeros(cfg, 1, x.shape[1])
        return PM.prefill_attn_layer(cfg, lay, cl, x, pos)[0], routes
    return jax.jit(f)


def _reference_layer(m: dict, moe: bool):
    """(weights, x (T, d)) -> (x', routes (T, k) or None), float32."""
    import jax

    from bench.arch import mla_moe as R
    mkey = tuple(sorted(m.items()))

    def f(w, x):
        routes = None
        if moe:
            x1 = x + R._attention(m, w, R._rms(x, w["ln1/scale"], m["norm_eps"]),
                                  False)
            routes = R.route(m, w, R._rms(x1, w["ln2/scale"], m["norm_eps"]))[0]
        return R.block(mkey, w, x, moe, False), routes
    return jax.jit(f)


def _differ(a, b):
    """Share of rows whose sets of ids differ."""
    import numpy as np
    return float(np.mean(np.any(np.sort(a, -1) != np.sort(b, -1), -1)))


def _update_err(x_in, prog, ref, agree):
    """Median over the agreeing rows of |prog - x_in - (ref - x_in)| /
    |ref - x_in|."""
    import numpy as np
    d_ref = ref - x_in
    err = (np.linalg.norm(prog - ref, axis=-1)
           / np.maximum(np.linalg.norm(d_ref, axis=-1), 1e-30))
    return float(np.median(err[agree])) if agree.any() else None


def readings(config: dict, traffic: dict, seed: int, *, routes=False,
             serve=False, control=False, faults=(), log=print) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import arch as A
    from bench import harness, mix
    from bench import weights as W
    from repro.models import model as PM

    arch = A.load(config)
    model, emb = config["model"], config["program"]["embed_rows"]
    cfg = harness.program_config(config, arch)
    out = {"seed": seed, "routed_down_scale": model["routed_down_scale"]}
    t0 = time.perf_counter()
    params = arch.nest(W.make_flat(seed, arch.weight_groups(model, emb)), model)

    loop = mix.ClosedLoop(traffic, seed, model["vocab_size"])
    done = [loop.next(i % loop.n_clients) for i in range(SPECS)]
    longest = max(done, key=lambda s: (len(s.prompt) + s.out_len, -s.index))
    rest = [s for s in done if s is not longest]
    pick = np.random.default_rng([seed, 11]).choice(
        len(rest), min(int(traffic["sample"]) - 1, len(rest)), replace=False)
    picked = [longest] + [rest[i] for i in sorted(pick)]

    pre = jax.jit(lambda p, b, n: PM.prefill(cfg, p, b, n), static_argnums=2)
    dec = jax.jit(lambda p, c, t, pos: PM.decode_step(cfg, p, c, t, pos))

    def served_by(p):
        toks = []
        for s in picked:
            S = len(s.prompt)
            lg, cache = pre(p, {"tokens": jnp.asarray(s.prompt)[None]},
                            S + s.out_len)
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
            seq = [tok]
            for i in range(s.out_len - 1):
                lg, cache = dec(p, cache, tok, jnp.int32(S + i))
                tok = jnp.argmax(lg, -1).astype(jnp.int32)
                seq.append(tok)
            toks.append(np.asarray(jnp.concatenate(seq)))
        return toks

    def gap(toks, with_control=False):
        g = harness.served_gaps(arch, seed, model, emb,
                                [s.prompt for s in picked], toks, with_control)
        r = {"gap": max(float(v.max()) for v in g["gap"])}
        if with_control:
            r["control"] = max(float(v.max()) for v in g["control_gap"])
        return r

    seq = longest.prompt
    if serve:
        served = served_by(params)
        g = gap(served, control)
        out["logit_gap"] = g["gap"]
        if control:
            out["control_gap"] = g["control"]
        out["served_tokens"] = int(sum(len(t) for t in served))
        log(json.dumps(out))
        seq = np.concatenate([longest.prompt, served[0][:-1]])
        for name in faults:
            ffn = params["layers"]["ffn"]
            w = (jnp.zeros_like(ffn["w_down"]) if name == "drop"
                 else jnp.roll(ffn["w_down"], 1, axis=1))
            bad = {**params, "layers": {**params["layers"],
                                        "ffn": {**ffn, "w_down": w}}}
            toks = served_by(bad)
            del bad, w      # the reference needs the room
            out[f"fault_{name}_gap"] = gap(toks)["gap"]
            log(json.dumps(out))
        log(f"seed {seed}: served and read in {time.perf_counter() - t0:.1f} s")
    if not routes:
        return {**out, "seconds": time.perf_counter() - t0}

    seq = jnp.asarray(np.asarray(seq, np.int32))
    pos = jnp.arange(seq.shape[0])[None]
    stem_specs, groups = arch.weight_groups(model, emb)
    x_ref = W.stem_f32(seed, stem_specs)["embed"][seq]
    x_prog = PM._embed(cfg, params, seq[None])
    free, forced, err = [], [], []
    for pk, _, n in PM._stacks(cfg):
        moe = pk == "layers"
        prog, ref = _program_layer(cfg, moe), _reference_layer(model, moe)
        group = next(g for g in groups if g[0] == pk)
        for i in range(n):
            lay = jax.tree.map(lambda a: a[i], params[pk])
            x_prog, r_free = prog(lay, x_prog, pos)
            y_forced, r_forced = prog(lay, x_ref.astype(x_prog.dtype)[None], pos)
            y_ref, r_ref = ref(W.layer_f32(seed, group, i), x_ref)
            x_in = np.asarray(x_ref, np.float32)
            if moe:
                r_ref = np.asarray(r_ref)
                free.append(_differ(np.asarray(r_free), r_ref))
                forced.append(_differ(np.asarray(r_forced), r_ref))
                agree = np.all(np.sort(np.asarray(r_forced), -1)
                               == np.sort(r_ref, -1), -1)
            else:
                agree = np.ones(x_in.shape[0], bool)
            err.append(_update_err(x_in, np.asarray(y_forced[0], np.float32),
                                   np.asarray(y_ref, np.float32), agree))
            x_ref = y_ref
    out.update(tokens=int(seq.shape[0]), free_differ=free,
               forced_differ=forced, forced_err=err,
               seconds=time.perf_counter() - t0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--down-scale", type=float)
    ap.add_argument("--routes", action="store_true")
    ap.add_argument("--serve", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.run import BENCH, find_cell, load_json
    if jax.devices()[0].platform != "tpu" and not os.environ.get("JAX_PLATFORMS"):
        print("needs a TPU", file=sys.stderr)
        return 2
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    if args.down_scale is not None:
        config["model"]["routed_down_scale"] = args.down_scale
    faults = [f for f in args.faults.split(",") if f]
    if not set(faults) <= {"drop", "roll"}:
        ap.error(f"unknown fault in {faults}")
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(config, traffic, seed, routes=args.routes,
                     serve=args.serve or bool(faults),
                     control=args.control, faults=faults,
                     log=lambda m: print(m, file=sys.stderr, flush=True))
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
