"""Training driver: sharded step, data prefetch, async checkpointing,
failure-injection-aware restart loop, straggler watchdog.

Runs the reduced configs end-to-end on CPU (tests/examples) and lowers the
full configs on the production mesh (dry-run). ``python -m repro.launch.train
--arch olmo-1b --steps 200 --reduced`` trains a real model.
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, latest_step
from repro.checkpoint.checkpoint import restore_into
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.data.pipeline import Prefetcher, make_batch
from repro.launch import sharding as shd
from repro.launch.mesh import batch_axes, make_local_mesh, model_axis
from repro.launch.train_step import make_optimizer, make_train_step
from repro.models import model as M
from repro.models import partitioning as part
from repro.runtime.fault import FailureInjector, SimulatedFailure, Watchdog


@dataclass
class TrainerConfig:
    batch_size: int = 8
    seq_len: int = 128
    steps: int = 50
    peak_lr: float = 3e-4
    warmup: int = 10
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 20
    log_every: int = 10
    watchdog_timeout: float = 120.0


class Trainer:
    def __init__(self, cfg: ModelConfig, tc: TrainerConfig,
                 mesh=None, injector: Optional[FailureInjector] = None):
        self.cfg = cfg
        self.tc = tc
        self.mesh = mesh or make_local_mesh()
        self.injector = injector
        self.history: List[Dict[str, float]] = []
        self.restarts = 0

        opt_init, opt_update = make_optimizer(
            cfg, tc.peak_lr, tc.warmup, max(tc.steps, 1))
        self._opt_init = opt_init
        self.step_fn = jax.jit(
            make_train_step(cfg, opt_update), donate_argnums=(0, 1))
        self.ckpt = (CheckpointManager(tc.ckpt_dir, every=tc.ckpt_every)
                     if tc.ckpt_dir else None)

        ba = batch_axes(self.mesh)
        self._act_axes = (ba if len(ba) > 1 else (ba[0] if ba else None),
                          model_axis(self.mesh))

    # ------------------------------------------------------------ lifecycle
    def init_state(self):
        params = M.init_params(self.cfg, jax.random.PRNGKey(0))
        opt = self._opt_init(params)
        return {"params": params, "opt_mu": opt.mu, "opt_nu": opt.nu,
                "opt_step": opt.step}, 0

    def restore_or_init(self):
        template, _ = self.init_state()
        if self.tc.ckpt_dir and latest_step(self.tc.ckpt_dir) is not None:
            step, state = restore_into(template, self.tc.ckpt_dir)
            return state, step
        return template, 0

    # ------------------------------------------------------------ run loops
    def run(self, steps: Optional[int] = None) -> Dict[str, Any]:
        from repro.optim.adamw import AdamWState
        steps = steps or self.tc.steps
        state, start = self.restore_or_init()
        params = state["params"]
        opt = AdamWState(state["opt_step"], state["opt_mu"], state["opt_nu"])
        wd = Watchdog(timeout=self.tc.watchdog_timeout)
        pf = Prefetcher(self.cfg, self.tc.batch_size, self.tc.seq_len,
                        start_step=start)
        next_step = start
        try:
            with part.activation_axes(*self._act_axes), jax.set_mesh(self.mesh):
                for _ in range(start, steps):
                    step_idx, batch = next(pf)
                    t0 = time.perf_counter()
                    if self.injector is not None:
                        self.injector.maybe_fail(step_idx)
                    params, opt, metrics = self.step_fn(params, opt, batch)
                    metrics = {k: float(v) for k, v in metrics.items()}
                    metrics["step"] = step_idx
                    metrics["step_s"] = time.perf_counter() - t0
                    self.history.append(metrics)
                    next_step = step_idx + 1
                    wd.beat(step_idx)
                    if self.ckpt:
                        self.ckpt.save(step_idx + 1, {
                            "params": params, "opt_mu": opt.mu,
                            "opt_nu": opt.nu, "opt_step": opt.step})
                    if step_idx % self.tc.log_every == 0:
                        print(f"step {step_idx}: loss={metrics['loss']:.4f} "
                              f"({metrics['step_s']*1e3:.0f}ms)", flush=True)
        finally:
            pf.stop()
            wd.stop()
            if self.ckpt:
                self.ckpt.save(next_step,
                               {"params": params, "opt_mu": opt.mu,
                                "opt_nu": opt.nu, "opt_step": opt.step},
                               force=True)
                self.ckpt.wait()
        return {"params": params, "opt": opt, "history": self.history}

    def run_with_restarts(self, max_restarts: int = 3) -> Dict[str, Any]:
        """Supervisor loop: every SimulatedFailure triggers restore+resume."""
        while True:
            try:
                return self.run()
            except SimulatedFailure:
                self.restarts += 1
                if self.restarts > max_restarts:
                    raise


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (CPU-sized) config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--moe-impl", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.moe_impl:
        cfg = cfg.replace(moe_impl=args.moe_impl)
    tc = TrainerConfig(batch_size=args.batch_size, seq_len=args.seq_len,
                       steps=args.steps, ckpt_dir=args.ckpt_dir)
    tr = Trainer(cfg, tc)
    out = tr.run_with_restarts()
    losses = [h["loss"] for h in out["history"]]
    print(f"done: first loss={losses[0]:.4f} last loss={losses[-1]:.4f}")


if __name__ == "__main__":
    main()
