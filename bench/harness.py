"""One benchmark cell: set-up, the measured window, and the check.

The system under test is the serving path of ``launch/serve.py``: an
``MRM`` over a ``DiskStore``, ``InferenceEngine(disk, mrm, use_trims=True)``
and ``ServingWorkers`` with their lookahead prefetch. The benchmark makes the
weights from the seed, publishes them to a store in a temporary directory,
warms every shape its traffic sends, and then offers the traffic for the
window. It records its own spans around each request's queue wait and
``generate``; with tracing on, the same spans go into the profiler's trace.
"""
from __future__ import annotations

import gc
import shutil
import statistics
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from bench import arch as A, mix, weights as W

GRACE_S = 60.0          # an answer due in the window may come this much later
MODEL_VERSION = "1"


def variant_name(config: dict, v: int) -> str:
    return f"{config['program']['arch']}.v{v}"


def program_config(config: dict, arch):
    """The program's own config for this configuration, built as
    ``launch/serve.py`` builds it; refused if it does not show the fields
    the architecture module asks of the configuration file."""
    from repro.launch.serve import serving_config

    prog = config["program"]
    if config["model"]["dtype"] != "bfloat16":
        raise ValueError(f"weights are made in bfloat16, not "
                         f"{config['model']['dtype']}")
    pc = serving_config(prog["arch"], reduced=bool(prog.get("reduced")))
    want = arch.program_fields(config)
    got = {k: getattr(pc, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"program config for {prog['arch']} departs from "
                         f"the configuration file (program, file): {bad}")
    return pc


class _Compiles:
    """Counts programs built (compiled or loaded from the persistent cache)
    while ``active``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT and self.active:
            self.count += 1


@dataclass
class Window:
    """What one measured window produced."""
    seconds: float
    opened: float                       # perf_counter at window open
    closed: float
    requests: List[mix.Spec]
    mrm_before: dict
    mrm_after: dict
    compiles: int
    lateness_s: List[float] = field(default_factory=list)


class Cell:
    """A configuration under a traffic mix, set up once, measured in windows."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 log: Callable[[str], None] = print):
        self.config, self.traffic, self.seed, self.log = config, traffic, seed, log
        self.arch = A.load(config)
        self.model = config["model"]
        self.embed_rows = config["program"]["embed_rows"]
        self.n_variants = int(traffic["variants"])
        self.names = [variant_name(config, v) for v in range(self.n_variants)]
        self.expected: Dict[int, dict] = {}     # variant -> fingerprints
        self.store_dir: Optional[str] = None
        self.mrm = self.engine = self.workers = None
        self.tracing = False
        self._by_tokens: Dict[int, mix.Spec] = {}
        self._compiles = _Compiles()

    # ---------------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.core import DiskStore, MRM
        from repro.core.costmodel import HardwareModel
        from repro.serving import InferenceEngine, ServingWorkers, publish_model

        t0 = time.perf_counter()
        pc = program_config(self.config, self.arch)
        layout = self.arch.weight_groups(self.model, self.embed_rows)
        serving = self.traffic["serving"]
        self.store_dir = tempfile.mkdtemp(prefix="bench-store-")
        disk = DiskStore(self.store_dir)
        # datasheet constants: they only feed modeled timings, and measuring
        # them would write outside the checkout
        self.mrm = MRM(disk, device_capacity=int(
            serving["device_capacity_gib"] * 2 ** 30), policy="lru",
            hw=HardwareModel())
        fills = []

        def publish(v: int, params: dict) -> None:
            publish_model(disk, pc, params, name=self.names[v],
                          version=MODEL_VERSION)
            if serving.get("host_fill"):
                fills.append(self.mrm.prefetch(self._key(self.names[v]),
                                               tier="host"))

        # variant v is written to the store (and read into the host tier)
        # while variant v + 1 is made and copied off the device
        with ThreadPoolExecutor(max_workers=1) as pool:
            pending = None
            for v in range(self.n_variants):
                flat = W.make_flat(self.seed + v, layout)
                self.expected[v] = W.fingerprints(flat)
                params = self.arch.nest(jax.device_get(flat), self.model)
                del flat
                if pending is not None:
                    pending.result()
                pending = pool.submit(publish, v, params)
                del params
            pending.result()
        t1 = time.perf_counter()
        for f in fills:
            f.result()
        t2 = time.perf_counter()
        self.engine = InferenceEngine(disk, self.mrm, use_trims=True)
        self._instrument()
        self.workers = ServingWorkers(self.engine, int(serving["workers"]))
        self._warm_up()
        self.log(f"set-up phases: weights made and published {t1 - t0:.3f} s, "
                 f"host tier filled {t2 - t1:.3f} s, warm-up "
                 f"{time.perf_counter() - t2:.3f} s")

    def _key(self, name: str):
        from repro.core.mrm import ModelKey
        from repro.serving import FRAMEWORK
        return ModelKey(FRAMEWORK, name, MODEL_VERSION)

    def _warm_up(self) -> None:
        """Every (prompt, output) shape of the traffic, prefill and decode,
        once, through the workers; models are touched in popularity order so
        the device tier starts with the most popular ones."""
        rng = np.random.default_rng([self.seed, 7])
        for i, (plen, out) in enumerate(mix.shapes(self.traffic)):
            spec = mix.Spec(-1 - i, i % self.n_variants,
                            rng.integers(0, self.model["vocab_size"], plen,
                                         dtype=np.int32), out)
            req = self._submit(spec)
            req.done.wait()
            if spec.error:
                raise RuntimeError(f"warm-up request {plen}x{out}: {spec.error}")

    def _instrument(self) -> None:
        """Wrap the engine's ``generate`` (the call each worker makes) in the
        benchmark's own spans."""
        inner = self.engine.generate

        def generate(name, tokens, max_new_tokens=8, version=MODEL_VERSION):
            spec = self._by_tokens[id(tokens)]
            spec.gen_start = time.perf_counter()
            qw = spec.extra.pop("queue_wait", None)
            if qw is not None:
                qw.__exit__(None, None, None)
            ann = (jax.profiler.TraceAnnotation("generate", req=spec.index)
                   if self.tracing else None)
            try:
                if ann is not None:
                    ann.__enter__()
                out, st = inner(name, tokens, max_new_tokens, version)
                spec.gen_end = time.perf_counter()
                spec.tokens, spec.stats = np.asarray(out)[0], st
                return out, st
            except Exception as e:  # noqa: BLE001 — recorded, then re-raised
                spec.gen_end = time.perf_counter()
                spec.error = repr(e)
                raise
            finally:
                if ann is not None:
                    ann.__exit__(None, None, None)

        self.engine.generate = generate

    def _submit(self, spec: mix.Spec):
        from repro.serving import Request

        tokens = spec.prompt[None, :]
        self._by_tokens[id(tokens)] = spec
        spec.extra["tokens"] = tokens          # keeps the id unique while live
        if self.tracing:
            qw = jax.profiler.TraceAnnotation("queue_wait", req=spec.index)
            qw.__enter__()
            spec.extra["queue_wait"] = qw
        return self.workers.submit(Request(self.names[spec.variant], tokens,
                                           spec.out_len))

    # ---------------------------------------------------------------- window
    def run_window(self, seconds: float, trace_dir: Optional[str] = None,
                   rate_per_s: Optional[float] = None) -> Window:
        """Offer the traffic for ``seconds``; wait up to ``GRACE_S`` past the
        close for every request sent. ``rate_per_s`` overrides the traffic's
        open-loop rate (the knee sweep)."""
        traffic = self.traffic
        if rate_per_s is not None:
            traffic = {**traffic, "arrivals": {**traffic["arrivals"],
                                               "rate_per_s": rate_per_s}}
        arr = mix.arrivals(traffic)
        vocab = self.model["vocab_size"]
        window_ann = None
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            self.tracing = True
            window_ann = jax.profiler.TraceAnnotation("window")
            window_ann.__enter__()
        gc.collect()
        before = self.mrm.stats()
        self._compiles.count, self._compiles.active = 0, True
        sent: List[tuple] = []
        lateness: List[float] = []
        try:
            if arr.LOOP == "open":
                specs = mix.open_loop(traffic, seconds, self.seed, vocab)
                opened = time.perf_counter()
                closes = opened + seconds
                for spec in specs:
                    due = opened + spec.due
                    now = time.perf_counter()
                    if due > now:
                        time.sleep(due - now)
                    lateness.append(max(0.0, time.perf_counter() - due))
                    spec.due = due
                    sent.append((spec, self._submit(spec)))
            else:
                loop = mix.ClosedLoop(traffic, self.seed, vocab)
                opened = time.perf_counter()
                closes = opened + seconds
                lock = threading.Lock()

                def client(c: int) -> None:
                    while time.perf_counter() < closes:
                        with lock:
                            spec = loop.next(c)
                            spec.due = time.perf_counter()
                            req = self._submit(spec)
                            sent.append((spec, req))
                        req.done.wait(seconds + GRACE_S)

                threads = [threading.Thread(target=client, args=(c,),
                                            name=f"bench-client-{c}")
                           for c in range(loop.n_clients)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(seconds + 2 * GRACE_S)
            now = time.perf_counter()
            if now < closes:
                time.sleep(closes - now)
            closed = time.perf_counter()
            for spec, req in sent:
                if not req.done.wait(max(0.0, closed + GRACE_S
                                         - time.perf_counter())):
                    spec.error = spec.error or "no answer within the grace period"
            self._compiles.active = False
            after = self.mrm.stats()
        finally:
            self._compiles.active = False
            if trace_dir is not None:
                window_ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                self.tracing = False
        for spec, _ in sent:
            spec.extra.clear()
        self._by_tokens.clear()
        return Window(seconds, opened, closed, [s for s, _ in sent], before,
                      after, self._compiles.count, lateness)

    # ----------------------------------------------------------------- check
    def check_weights(self, tiers) -> int:
        """Tensors whose fingerprint differs from the seeded weights', over
        every copy the MRM holds on the given tiers ("device", "host")."""
        from repro.core.cache import Tier

        off = 0
        for v, name in enumerate(self.names):
            key = self._key(name)
            for tier in tiers:
                if not self.mrm.resident(key, Tier.DEVICE if tier == "device"
                                         else Tier.HOST):
                    continue
                h = self.mrm.open(key, tier=tier)
                try:
                    got = W.fingerprints(h.weights)
                finally:
                    self.mrm.close(h)
                want = self.expected[v]
                bad = sorted(n for n in want if got.get(n) != want[n])
                bad += sorted(n for n in got if n not in want)
                if bad:
                    self.log(f"weights of {name} on the {tier} tier differ "
                             f"from the seed's: {bad[:5]}")
                off += len(bad)
        return off

    def release(self) -> None:
        """Stop the workers and free every model copy the MRM holds."""
        if self.workers is not None:
            self.workers.stop()
            self.workers = None
        if self.mrm is not None:
            for name in self.names:
                self.mrm.drop_model(self._key(name))
            self.mrm.shutdown()
        self.mrm = self.engine = None
        gc.collect()

    def close(self) -> None:
        self.release()
        if self.store_dir is not None:
            shutil.rmtree(self.store_dir, ignore_errors=True)
            self.store_dir = None

    def sample(self, done: List[mix.Spec], k: int) -> List[mix.Spec]:
        """``k`` finished requests drawn from the seed, the longest among
        them."""
        if not done:
            return []
        longest = max(done, key=lambda s: (len(s.prompt) + s.out_len, -s.index))
        rest = [s for s in done if s is not longest]
        rng = np.random.default_rng([self.seed, 11])
        pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
        return [longest] + [rest[i] for i in sorted(pick)]

    def logit_gaps(self, picked: List[mix.Spec], control: bool = False
                   ) -> Dict[str, float]:
        """The widest gap over the sample's served tokens (and, with
        ``control``, the float8 control's) against the reference, and the
        number of positions whose token is not the reference's best."""
        out = {"gap": 0.0, "tokens": 0, "flips": 0}
        if control:
            out["control_gap"], out["control_flips"] = 0.0, 0
        for v in sorted({s.variant for s in picked}):
            group = [s for s in picked if s.variant == v]
            r = served_gaps(self.arch, self.seed + v, self.model,
                            self.embed_rows, [s.prompt for s in group],
                            [s.tokens for s in group], control)
            out["gap"] = max([out["gap"]] + [float(g.max()) for g in r["gap"]])
            out["tokens"] += sum(len(s.tokens) for s in group)
            out["flips"] += sum(int((g > 0).sum()) for g in r["gap"])
            if control:
                out["control_gap"] = max([out["control_gap"]] + [
                    float(g.max()) for g in r["control_gap"]])
                out["control_flips"] += sum(int((g > 0).sum())
                                            for g in r["control_gap"])
        return out


def served_gaps(arch, seed: int, model: dict, embed_rows: int,
                prompts: Sequence[np.ndarray], served: Sequence[np.ndarray],
                control: bool = False) -> Dict[str, List[np.ndarray]]:
    """The architecture's reference over each prompt followed by its served
    tokens.

    Returns, per request, ``gap``: how far each served token's float32 logit
    lies below the reference's best at its position; with ``control``, also
    ``control_gap``: the same for the token the float8 control puts first."""
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32)
            for p, s in zip(prompts, served)]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(s)) for p, s in
            zip(prompts, served)]
    ref = arch.logits_at(seed, model, embed_rows, seqs, rows)
    out = {"gap": [r.max(-1) - r[np.arange(len(s)), s]
                   for r, s in zip(ref, served)]}
    if control:
        low = arch.logits_at(seed, model, embed_rows, seqs, rows, fp8=True)
        out["control_gap"] = [r.max(-1) - r[np.arange(len(r)), c.argmax(-1)]
                              for r, c in zip(ref, low)]
    return out


# ------------------------------------------------------------ end to end
def _pct(xs: List[float], q: float) -> Optional[float]:
    return float(np.percentile(xs, q)) if xs else None


def end_to_end(win: Window) -> Dict[str, Optional[float]]:
    """The user-facing metrics of a window, over every request sent in it:
    time to first token and to the last token, measured from when the
    request was due (open loop) or sent (closed loop), and the output
    tokens completed inside the window per second of it."""
    ok = [s for s in win.requests if s.error is None and s.stats is not None]
    ttft = [(s.gen_start + s.stats.ttft_s - s.due) * 1e3 for s in ok]
    lat = [(s.gen_end - s.due) * 1e3 for s in ok]
    toks = sum(s.out_len for s in ok if s.gen_end <= win.closed)
    return {"ttft_p50_ms": _pct(ttft, 50), "ttft_p95_ms": _pct(ttft, 95),
            "latency_p95_ms": _pct(lat, 95),
            "tokens_per_s": toks / win.seconds}


def describe(win: Window) -> Dict[str, float]:
    """Counts that say how the window went, beside the metrics."""
    ok = [s for s in win.requests if s.error is None and s.stats is not None]
    tiers: Dict[str, int] = {}
    for s in ok:
        tiers[s.stats.tier_hit] = tiers.get(s.stats.tier_hit, 0) + 1
    backlog = sum(1 for s in win.requests
                  if s.error is None and s.gen_end > win.closed)
    late = win.lateness_s
    return {"sent": len(win.requests), "answered": len(ok),
            "finished_in_window": sum(1 for s in ok if s.gen_end <= win.closed),
            "backlog_at_close": backlog,
            "generator_late_p95_ms": (_pct(late, 95) or 0.0) * 1e3,
            "tiers": tiers,
            "compiles_in_window": win.compiles,
            "queue_wait_median_ms": statistics.median(
                [(s.gen_start - s.due) * 1e3 for s in ok]) if ok else 0.0}
