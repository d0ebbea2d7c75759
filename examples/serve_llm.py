"""End-to-end LLM serving driver: publish real models from the zoo, serve
batched generate() requests through the TrIMS-backed engine, and compare the
FaaS cold-start baseline against warm shared serving.

    PYTHONPATH=src python examples/serve_llm.py [--arch olmo-1b] [--requests 4]

The model is published at its published widths with seeded weights; pass
``--reduced`` for the tiny config that runs in seconds on a CPU. Exits
non-zero if any request fails.
"""
import argparse
import sys
import tempfile
import time

import jax
import numpy as np

from repro.configs import list_archs
from repro.core import DiskStore, MRM
from repro.launch.serve import serving_config
from repro.models import init_params
from repro.serving import InferenceEngine, Request, ServingWorkers, publish_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny reduced config (CPU rehearsal)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    root = tempfile.mkdtemp(prefix="trims_serve_")
    disk = DiskStore(f"{root}/models")
    cfg = serving_config(args.arch, reduced=args.reduced)
    print(f"publishing {args.arch} (d_model={cfg.d_model}, "
          f"{cfg.param_count()/1e6:.1f}M params)")
    publish_model(disk, cfg, init_params(cfg, jax.random.PRNGKey(0)),
                  name=args.arch)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size - 1, size=(args.batch, args.prompt_len)).astype(np.int32)

    for use_trims in (False, True):
        mrm = MRM(disk, device_capacity=8 << 30) if use_trims else None
        engine = InferenceEngine(disk, mrm, use_trims=use_trims)
        label = "TrIMS" if use_trims else "baseline(cold)"
        t0 = time.perf_counter()
        for i in range(args.requests):
            out, st = engine.generate(args.arch, toks, args.max_new)
            print(f"  [{label}] req{i}: load={st.model_load_s*1e3:7.1f}ms "
                  f"compute={st.compute_s*1e3:7.1f}ms tier={st.tier_hit} "
                  f"tokens={out[0][:4].tolist()}...")
        wall = time.perf_counter() - t0
        print(f"  [{label}] {args.requests} requests in {wall:.2f}s\n")

    # concurrent serving through the worker pool
    mrm = MRM(disk, device_capacity=8 << 30)
    engine = InferenceEngine(disk, mrm)
    workers = ServingWorkers(engine, n_workers=4)
    reqs = [workers.submit(Request(model=args.arch, tokens=toks,
                                   max_new=args.max_new))
            for _ in range(args.requests * 2)]
    t0 = time.perf_counter()
    workers.drain(reqs)
    wall = time.perf_counter() - t0
    workers.stop()
    failed = [r.result for r in reqs if isinstance(r.result, Exception)]
    print(f"concurrent: {len(reqs) - len(failed)}/{len(reqs)} requests ok in "
          f"{wall:.2f}s, disk loads={mrm.stats()['disk_loads']}, "
          f"exe cache hits={engine.exe_cache_hits}")
    for exc in failed[:3]:
        print(f"  failed: {exc!r}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
