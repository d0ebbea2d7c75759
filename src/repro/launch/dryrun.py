import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (architecture x shape x mesh)
cell on 512 placeholder devices; capture memory/cost/collective analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

The two lines above MUST stay the first statements in this module: jax locks
the device count on first init, and only the dry-run may see 512 devices.
"""

import argparse
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs import (ARCHS, SHAPES_BY_NAME, cell_applicable, get_config,
                           list_archs)
from repro.launch import sharding as shd
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import (batch_axes, make_production_mesh, model_axis,
                               n_chips)
from repro.launch.specs import input_specs
from repro.launch.train_step import (make_decode_step, make_optimizer,
                                     make_prefill_step, make_train_step)
from repro.models import partitioning as part

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "benchmarks", "artifacts", "dryrun")


def _cpu_bf16_staging(hlo: str, args, in_sh) -> dict:
    """Detect XLA:CPU fp32 staging twins of bf16 argument buffers.

    For every bf16 array argument leaf whose per-device LOCAL shape also
    appears as an f32 HLO buffer, count the f32 twin (2x the bf16 bytes)
    per distinct shape and estimate the traffic its reference sites add.
    (Two buffers per shape: k & v share one shape and both get staged.)"""
    import jax as _jax
    import numpy as _np

    arg_leaves = _jax.tree.leaves(args)
    sh_leaves = _jax.tree.leaves(in_sh, is_leaf=lambda x: hasattr(x, "shard_shape"))
    seen = set()
    total_bytes = 0
    traffic = 0.0
    for leaf, sh in zip(arg_leaves, sh_leaves):
        if getattr(leaf, "dtype", None) is None or str(leaf.dtype) != "bfloat16":
            continue
        try:
            local = sh.shard_shape(leaf.shape)
        except Exception:  # noqa: BLE001
            local = leaf.shape
        dims = ",".join(str(d) for d in local)
        if dims in seen or not dims:
            continue
        seen.add(dims)
        refs = hlo.count(f"f32[{dims}]")
        if refs == 0:
            continue
        f32_bytes = int(_np.prod(local)) * 4
        total_bytes += 2 * f32_bytes
        traffic += refs * f32_bytes
    return {"bytes": total_bytes, "traffic": traffic}


def build_step_fn(cfg, shape):
    if shape.kind == "train":
        _, opt_update = make_optimizer(cfg)
        return make_train_step(cfg, opt_update), (0, 1)  # donate params, opt
    if shape.kind == "prefill":
        return make_prefill_step(cfg, max_len=shape.seq_len), ()
    return make_decode_step(cfg), (1,)                   # donate cache


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             cfg_overrides: Optional[dict] = None,
             keep_hlo: bool = False) -> dict:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = SHAPES_BY_NAME[shape_name]
    rec = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
           "kind": shape.kind, "ok": False}
    if not cell_applicable(cfg, shape):
        rec.update(ok=True, skipped=True,
                   reason="long_500k needs sub-quadratic attention "
                          "(see DESIGN.md §4)")
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    step_fn, donate = build_step_fn(cfg, shape)
    args, in_sh, out_sh = input_specs(cfg, shape, mesh)
    ba = batch_axes(mesh)
    ba = ba if len(ba) > 1 else (ba[0] if ba else None)

    t0 = time.time()
    with part.activation_axes(ba, model_axis(mesh)), jax.set_mesh(mesh):
        lowered = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()          # per-device numbers
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # older jax wraps the dict in a list
        ca = ca[0] if ca else {}
    hlo = compiled.as_text()
    summary = analyze_hlo(hlo, default_group_size=n_chips(mesh))
    # gradients make fp32 twins of param shapes legitimate in train cells;
    # only inference cells get the CPU-staging correction
    staging = (_cpu_bf16_staging(hlo, args, in_sh) if shape.kind != "train"
               else {"bytes": 0, "traffic": 0.0})
    if keep_hlo:
        rec["hlo_path"] = os.path.join(ARTIFACT_DIR, f"{arch}.{shape_name}."
                                       f"{'mp' if multi_pod else 'sp'}.hlo")
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        with open(rec["hlo_path"], "w") as f:
            f.write(hlo)

    rec.update(
        ok=True,
        chips=n_chips(mesh),
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        per_device={
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_hbm_bytes": (ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               + ma.temp_size_in_bytes
                               - ma.alias_size_in_bytes),
            # XLA:CPU promotes bf16 loop buffers to fp32 staging copies
            # (reproduced with a minimal bf16 DUS scan on 1 device); TPU
            # keeps them bf16. Subtract the measured staging to get the
            # TPU-representative peak. See EXPERIMENTS.md §Dry-run.
            "cpu_bf16_staging_bytes": staging["bytes"],
            "peak_hbm_bytes_tpu": (ma.argument_size_in_bytes
                                   + ma.output_size_in_bytes
                                   + ma.temp_size_in_bytes
                                   - ma.alias_size_in_bytes
                                   - staging["bytes"]),
            "staging_traffic_bytes": staging["traffic"],
        },
        xla_cost={"flops_body_once": ca.get("flops"),
                  "bytes_body_once": ca.get("bytes accessed")},
        hlo_analysis=summary.to_dict(),
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=sorted(SHAPES_BY_NAME))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--override", default=None,
                    help="json dict of ModelConfig overrides")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    overrides = json.loads(args.override) if args.override else None

    cells = []
    if args.all:
        for arch in list_archs():
            for shape_name in sorted(SHAPES_BY_NAME):
                cells.append((arch, shape_name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_fail = 0
    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch}.{shape_name}.{'mp' if mp else 'sp'}"
            out_path = os.path.join(args.out, tag + ".json")
            try:
                rec = run_cell(arch, shape_name, mp, overrides,
                               keep_hlo=args.keep_hlo)
            except Exception as e:  # noqa: BLE001
                rec = {"arch": arch, "shape": shape_name, "multi_pod": mp,
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                n_fail += 1
            with open(out_path, "w") as f:
                json.dump(rec, f, indent=1)
            status = ("SKIP" if rec.get("skipped")
                      else "OK" if rec.get("ok") else "FAIL")
            extra = ""
            if rec.get("ok") and not rec.get("skipped"):
                pk = rec["per_device"]["peak_hbm_bytes"] / 2 ** 30
                extra = (f" compile={rec['compile_s']}s"
                         f" peak_hbm={pk:.2f}GiB"
                         f" coll={rec['hlo_analysis']['total_coll_bytes']/2**30:.2f}GiB")
            print(f"[{status}] {tag}{extra}", flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
