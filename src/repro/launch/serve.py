"""Serving launcher: stand up the TrIMS MRM (+ optional cross-process shm
server) and drive an inference engine over the published model store.

  PYTHONPATH=src python -m repro.launch.serve --store /path/to/models \\
      --arch olmo-1b --requests 4 [--reduced] [--no-trims] \\
      [--shm-socket /tmp/mrm.sock]

If the store does not hold ``--arch`` yet, the model is published first with
weights drawn from ``--seed`` (nothing is downloaded), at its published widths
— or at the tiny ``cfg.reduced()`` widths with ``--reduced``, for rehearsing
the path on a CPU. A store that already holds ``--arch`` under a different
config is refused.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.configs.base import ModelConfig
from repro.core import DiskStore, MRM
from repro.core.costmodel import get_hardware
from repro.core.mrm import ModelKey
from repro.models import init_params
from repro.serving import FRAMEWORK, InferenceEngine, publish_model

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already places the cache and is
    left alone; otherwise the cache lives at the fixed ``<repo>/.jax_cache``
    (a fixed path, since the path is part of what a later run must find)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def serving_config(arch: str, *, reduced: bool) -> ModelConfig:
    """The config ``arch`` is published and served under: its published
    widths, or ``cfg.reduced()`` for a CPU rehearsal. MoE serves through the
    dropless ``ragged`` path."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if cfg.n_experts:
        cfg = cfg.replace(moe_impl="ragged")
    return cfg


def ensure_published(disk: DiskStore, arch: str, cfg: ModelConfig,
                     seed: int) -> ModelConfig:
    """Publish ``arch`` with seeded weights unless the store holds it, and
    return the config the store holds; raise if it differs from ``cfg``."""
    key = ModelKey(FRAMEWORK, arch, "1")
    if not disk.contains(key):
        publish_model(disk, cfg, init_params(cfg, jax.random.PRNGKey(seed)),
                      name=arch)
    stored = ModelConfig(**disk.open(key).meta["config"])
    if stored != cfg:
        raise ValueError(
            f"store {disk.root} holds {arch} with d_model={stored.d_model}, "
            f"n_layers={stored.n_layers}, vocab={stored.vocab_size}; "
            f"asked for d_model={cfg.d_model}, n_layers={cfg.n_layers}, "
            f"vocab={cfg.vocab_size} — use a fresh --store")
    return stored


def serve_once(arch: str, *, reduced: bool, store_dir: str,
               requests: int = 2, batch: int = 1, prompt_len: int = 32,
               max_new: int = 8, seed: int = 0, use_trims: bool = True,
               baseline: bool = False, device_capacity: int = 8 << 30,
               policy: str = "lru", shm_socket: Optional[str] = None) -> dict:
    """Publish (if needed) and serve ``arch`` from ``store_dir``.

    Runs ``requests`` greedy generations of one seeded prompt through the
    engine — via the MRM unless ``use_trims`` is False — and, with
    ``baseline``, one more through a cold ``InferenceEngine(use_trims=False)``.
    Returns a dict with ``cfg`` (the config the store holds), ``prompt``,
    ``outputs`` and ``stats`` (one per request; ``stats.total_s`` ends once
    the tokens are on the host), ``baseline`` (its ``(tokens, stats)`` or
    None), ``mrm_stats`` and ``engine``."""
    disk = DiskStore(store_dir)
    cfg = ensure_published(disk, arch, serving_config(arch, reduced=reduced),
                           seed)
    prompt = np.random.default_rng(seed).integers(
        0, cfg.vocab_size - 1, size=(batch, prompt_len)).astype(np.int32)

    mrm = server = None
    if use_trims:
        mrm = MRM(disk, device_capacity=device_capacity, policy=policy,
                  hw=get_hardware(), use_shm=shm_socket is not None)
        if shm_socket:
            from repro.core.shm_ipc import MRMServer
            server = MRMServer(mrm, shm_socket)
    try:
        engine = InferenceEngine(disk, mrm, use_trims=use_trims)
        outputs, stats = [], []
        for _ in range(requests):
            out, st = engine.generate(arch, prompt, max_new)
            outputs.append(out)
            stats.append(st)
        base = None
        if baseline:
            base = InferenceEngine(disk, None, use_trims=False).generate(
                arch, prompt, max_new)
    finally:
        if server is not None:
            server.stop()
    return {"cfg": cfg, "prompt": prompt, "outputs": outputs, "stats": stats,
            "baseline": base, "engine": engine,
            "mrm_stats": mrm.stats() if mrm is not None else None}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--store", default="/tmp/trims_store")
    ap.add_argument("--arch", default="olmo-1b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny reduced config (CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--no-trims", action="store_true")
    ap.add_argument("--device-capacity-gb", type=float, default=8.0)
    ap.add_argument("--policy", default="lru",
                    choices=["lru", "lcu", "fifo", "largest"])
    ap.add_argument("--shm-socket", default=None,
                    help="also expose the MRM to other processes here")
    args = ap.parse_args()

    print(f"compile cache: {enable_compile_cache()}")
    r = serve_once(args.arch, reduced=args.reduced, store_dir=args.store,
                   requests=args.requests, batch=args.batch,
                   prompt_len=args.prompt_len, max_new=args.max_new,
                   seed=args.seed, use_trims=not args.no_trims,
                   device_capacity=int(args.device_capacity_gb * 2 ** 30),
                   policy=args.policy, shm_socket=args.shm_socket)
    cfg = r["cfg"]
    print(f"{args.arch}: d_model={cfg.d_model} n_layers={cfg.n_layers} "
          f"({cfg.param_count()/1e6:.1f}M params)")
    for i, st in enumerate(r["stats"]):
        print(f"req{i}: tier={st.tier_hit:<12} load={st.model_load_s*1e3:8.2f}ms "
              f"compile={st.compile_s*1e3:8.1f}ms "
              f"compute={st.compute_s*1e3:8.1f}ms total={st.total_s*1e3:8.1f}ms")
    s = r["mrm_stats"]
    if s is not None:
        print(f"MRM: {s['opens']} opens, {s['disk_loads']} disk loads, "
              f"device hits {s['device']['hits']}")


if __name__ == "__main__":
    main()
