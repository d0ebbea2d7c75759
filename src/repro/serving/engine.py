"""Inference engine: the FaaS-side consumer of TrIMS.

The engine executes prediction requests against models resolved through the
TrIMS client (warm path) or a cold disk load (the baseline every benchmark
compares against). Beyond the paper, the engine extends the MRM idea to the
OTHER TPU cold-start term: compiled executables are cached keyed by
(architecture-signature, batch, seq) — two models with identical topology
share one XLA program, exactly like weights share one HBM copy.

Latency accounting per request mirrors paper Fig. 1/9:
  model_load_s (disk+deserialize+H2D | share), compile_s, compute_s.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DENSE, ModelConfig
from repro.core.client import LoadedModel, TrimsClient, cold_load, free_model
from repro.core.mrm import MRM, ModelKey
from repro.core.store import DiskStore
from repro.models import model as M
from repro.runtime import spans
from repro.serving.weights_io import (flat_to_params, flat_to_params_like,
                                      params_to_flat)

FRAMEWORK = "repro-jax"


def arch_signature(cfg: ModelConfig) -> str:
    payload = json.dumps(dataclasses.asdict(cfg), sort_keys=True, default=str)
    return hashlib.sha1(payload.encode()).hexdigest()[:16]


def publish_model(disk: DiskStore, cfg: ModelConfig, params,
                  name: Optional[str] = None, version: str = "1") -> ModelKey:
    """Serialize a params tree into the store (deploy path / train export)."""
    key = ModelKey(FRAMEWORK, name or cfg.name, version)
    disk.put(key, params_to_flat(params),
             meta={"config": dataclasses.asdict(cfg)})
    return key


def _prefill_batch(cfg: ModelConfig, tokens: np.ndarray) -> Dict[str, Any]:
    """Prefill inputs for (B, S) prompt ``tokens``; families with a stubbed
    encoder/vision frontend get zero frontend embeddings."""
    B, S = tokens.shape
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    if cfg.family in ("vlm", "encdec"):
        batch["frontend"] = jnp.zeros(
            (B, cfg.n_frontend_tokens or S, cfg.d_model), jnp.float32)
    return batch


def _streamable(cfg: ModelConfig) -> bool:
    """Whether the layer-streaming path serves ``cfg``: one stack of GQA
    layers (a mixed layout or MLA takes the batch open)."""
    return (cfg.family in ("dense", "moe") and not cfg.first_k_dense
            and not cfg.kv_lora_rank)


def _moe_stats(cfg: ModelConfig, B: int, counts: np.ndarray) -> Dict[str, float]:
    """``engine.decode`` stats from a request's per-step MoE counts (steps,
    3): held experts that took a token, per MoE layer and step, the share
    of routed assignments that landed on held experts, and held experts
    whose weights were read, per MoE layer and step."""
    layer_steps = counts.shape[0] * cfg.n_moe_layers
    return {"experts_touched": float(counts[:, 0].sum()) / layer_steps,
            "held_share": float(counts[:, 1].sum())
            / (layer_steps * cfg.top_k * B),
            "experts_read": float(counts[:, 2].sum()) / layer_steps}


def _rows_step(cfg: ModelConfig, params, caches, toks, positions):
    """One decode step over B=1 rows of one model: each row's next token
    (1,) and new cache."""
    logits, new = M.decode_step_rows(cfg, params, caches,
                                     jnp.concatenate(toks), jnp.stack(positions))
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tuple(nxt[r:r + 1] for r in range(len(toks))), new


def _abstract(tree):
    """Shapes of ``tree``'s arrays, placed as they are: a program lowered
    for them is the one ``jit`` dispatches to for the arrays themselves."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding if a.committed else None), tree)


@dataclass(eq=False)
class _Row:
    """A B=1 request of a dense model in decode: its cache, the next
    position, the steps it has left and every token made so far (the last
    is the next step's input). ``busy`` while a thread launches its step."""
    key: ModelKey
    arch: str
    max_len: int
    params: Any
    solo: Tuple[Any, Any]         # (sig, exe) of its B=1 decode program
    cache: Any
    pos: int
    left: int
    out: List[Any]
    busy: bool = False
    error: Optional[BaseException] = None


@dataclass
class ServableModel:
    key: ModelKey
    cfg: ModelConfig
    params: Any
    loaded: LoadedModel
    nbytes: int


@dataclass
class RequestStats:
    model: str
    cold: bool
    tier_hit: str
    model_load_s: float
    compile_s: float
    compute_s: float
    total_s: float
    modeled_load_s: float = 0.0
    ttft_s: float = 0.0          # submit -> first token materialized
    streamed: bool = False       # served via the layer-streaming path (§9)


class InferenceEngine:
    def __init__(self, disk: DiskStore, mrm: Optional[MRM] = None,
                 use_trims: bool = True,
                 prefix_cache_bytes: int = 0,
                 streaming: bool = False):
        self.disk = disk
        self.mrm = mrm
        self.use_trims = use_trims and mrm is not None
        # streaming (DESIGN.md §9): serve DENSE/MOE requests layer by layer
        # against a partial open — prefill starts once stem+layer0 land.
        # Other families (or warm hits) fall back to the batch path.
        self.streaming = streaming and self.use_trims
        self.trims = TrimsClient(mrm, "engine") if self.use_trims else None
        # exe cache is keyed by architecture signature (not model identity) so
        # same-topology models share programs; the (B, S, max_len) tail keys
        # the actual traced shapes. cfg cache MUST key by (name, version) —
        # version "2" of a model may ship a different architecture.
        self._exe_cache: Dict[Tuple[str, str, int, int, int], Any] = {}
        self._exe_compiled: set = set()   # sigs whose first call was timed
        self._cfg_cache: Dict[Tuple[str, str], ModelConfig] = {}
        self._lock = threading.RLock()
        # same-model decode rows (dense, B=1): a step may take two rows whose
        # two-row program, keyed (arch signature, max_len, max_len), is built
        self._rows_cv = threading.Condition()
        self._decoding: Dict[ModelKey, List[_Row]] = {}
        self._row_specs: Dict[Tuple[str, int], Any] = {}
        self._row_exe: Dict[Tuple[str, int, int], Any] = {}
        self.exe_cache_hits = 0
        self.exe_cache_misses = 0
        self.prefix_kv = None
        if prefix_cache_bytes > 0:
            from repro.serving.prefix_cache import PrefixKVStore
            self.prefix_kv = PrefixKVStore(prefix_cache_bytes)

    # ------------------------------------------------------------- loading
    def _config_for(self, key: ModelKey) -> ModelConfig:
        mf = self.disk.open(key)
        raw = dict(mf.meta["config"])
        return ModelConfig(**raw)

    def load_model(self, name: str, version: str = "1"
                   ) -> Tuple[ServableModel, float]:
        """Resolve weights (TrIMS or cold) -> params tree. Returns
        (model, load_seconds)."""
        key = ModelKey(FRAMEWORK, name, version)
        cfg = self._cfg_cache.get((name, version)) or self._config_for(key)
        self._cfg_cache[(name, version)] = cfg
        with spans.request() as req:
            t0 = time.perf_counter()
            with spans.span("engine.open", req=req, model=name) as sp:
                if self.use_trims:
                    h = self.trims.open(FRAMEWORK, name, version)
                    loaded = LoadedModel(key, h.weights, h.nbytes, h.timings,
                                         via_trims=True, handle=h)
                else:
                    loaded = cold_load(self.disk, key)
                sp.set_metadata(tier=loaded.timings.tier_hit)
            load_s = time.perf_counter() - t0
            with spans.span("engine.params", req=req):
                template = jax.eval_shape(
                    lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
                params = flat_to_params_like(
                    template, loaded.weights,
                    convert=lambda v: v if hasattr(v, "devices")
                    else jnp.asarray(v))
        return ServableModel(key, cfg, params, loaded, loaded.nbytes), load_s

    def release(self, sm: ServableModel):
        free_model(sm.loaded, self.trims)

    def prefetch(self, name: str, version: str = "1"):
        """Warm the next model's weights toward the device tier in the
        background — issued by workers so the next request's load overlaps
        the current request's compute. No-op without TrIMS.

        Device-tier prefetch is gated on free HBM: staging into a full
        device tier would evict (or capacity-block) the model the *current*
        request is about to open. Without headroom we still warm the host
        tier — that is where the expensive disk+deserialize work lives.

        With ``streaming`` on, a model that is not yet disk-resident but is
        reachable (object store / cloud / peer hook) is warmed through a
        partial open instead (``MRM.open_stream``): when a request for it
        arrives mid-flight, its streaming open coalesces onto this one and
        inherits the per-window readiness already accumulated."""
        if not self.use_trims:
            return None
        key = ModelKey(FRAMEWORK, name, version)
        if not self.disk.contains(key):
            if self.streaming and self._fetchable(key):
                return self.mrm.open_stream(key, want_handle=False)
            return None
        tier = "device"
        try:
            if self.mrm.device.free_bytes() < self.disk.open(key).total_bytes:
                tier = "host"
        except Exception:  # noqa: BLE001 — a hint must never fail the worker
            tier = "host"
        return self.mrm.prefetch(key, tier=tier)

    def _fetchable(self, key: ModelKey) -> bool:
        m = self.mrm
        try:
            return ((m.objectstore is not None and m.objectstore.contains(key))
                    or (m.cloud is not None and m.cloud.contains(key))
                    or m.remote_fetch is not None)
        except Exception:  # noqa: BLE001 — a hint must never fail the worker
            return False

    # ------------------------------------------------------------- compile
    def _executable(self, cfg: ModelConfig, kind: str, B: int, S: int,
                    max_len: int) -> Tuple[Any, float, tuple]:
        """Executable cache keyed by topology signature, NOT model name —
        same-architecture models share one compiled program. ``max_len`` is
        part of the key: it is baked into the traced program.

        Returns ``(exe, trace_s, sig)``; XLA compiles on the first call,
        which :meth:`_run_exe` times against ``sig``."""
        sig = (arch_signature(cfg), kind, B, S, max_len)
        with self._lock:
            exe = self._exe_cache.get(sig)
        if exe is not None:
            self.exe_cache_hits += 1
            return exe, 0.0, sig
        self.exe_cache_misses += 1
        t0 = time.perf_counter()
        if kind == "prefill":
            exe = jax.jit(lambda p, b: M.prefill(cfg, p, b, max_len))
        elif kind == "decode":
            counted = cfg.n_experts > 0 and cfg.moe_impl == "ragged"
            exe = jax.jit(lambda p, c, t, pos: M.decode_step(
                cfg, p, c, t, pos, moe_counts=counted))
        elif kind == "sembed":
            exe = jax.jit(lambda p, t: M.stream_prefill_embed(cfg, p, t))
        elif kind == "slayer":
            exe = jax.jit(
                lambda l, x, pos: M.stream_prefill_layer(cfg, l, x, pos, max_len))
        elif kind == "slogits":
            exe = jax.jit(lambda p, x: M.stream_logits(cfg, p, x))
        elif kind == "sdembed":
            exe = jax.jit(lambda p, t: M.stream_decode_embed(cfg, p, t))
        elif kind == "sdlayer":
            exe = jax.jit(
                lambda l, x, c, pos: M.stream_decode_layer(cfg, l, x, c, pos))
        else:
            exe = jax.jit(lambda p, b: M.forward(cfg, p, b)[0])
        compile_s = time.perf_counter() - t0  # trace cost; XLA compile on 1st call
        with self._lock:
            self._exe_cache[sig] = exe
        return exe, compile_s, sig

    def _run_exe(self, sig: tuple, exe, *args) -> Tuple[Any, float]:
        """Run a cached executable, timing its FIRST execution (when XLA
        actually compiles) so compile cost lands in ``compile_s`` instead of
        polluting ``compute_s``. Returns ``(out, extra_compile_s)``."""
        with self._lock:
            first = sig not in self._exe_compiled
            if first:
                self._exe_compiled.add(sig)
        if not first:
            return exe(*args), 0.0
        t0 = time.perf_counter()
        out = exe(*args)
        jax.block_until_ready(out)
        return out, time.perf_counter() - t0

    # --------------------------------------------------------------- infer
    def generate(self, name: str, tokens: np.ndarray, max_new_tokens: int = 8,
                 version: str = "1") -> Tuple[np.ndarray, RequestStats]:
        """Prefill + greedy decode. tokens: (B, S) int32.

        With ``streaming`` on, cold DENSE/MOE loads are served layer by
        layer against a partial open (same tokens, earlier first token);
        anything else falls through to the batch path below.

        Its spans carry the request id the caller bound (``spans.request``),
        else the next one."""
        with spans.request():
            if self.streaming:
                r = self._generate_streaming(name, tokens, max_new_tokens,
                                             version)
                if r is not None:
                    return r
            return self._generate_batch(name, tokens, max_new_tokens, version)

    def prefill_logits(self, name: str, tokens: np.ndarray,
                       max_new_tokens: int = 8, version: str = "1"
                       ) -> np.ndarray:
        """Last-position prefill logits (B, V) as float32, through the same
        load path and cached prefill program as :meth:`generate`."""
        sm, _ = self.load_model(name, version)
        try:
            B, S = tokens.shape
            exe, _, sig = self._executable(sm.cfg, "prefill", B, S,
                                           S + max_new_tokens)
            (logits, _), _ = self._run_exe(sig, exe, sm.params,
                                           _prefill_batch(sm.cfg, tokens))
            return np.asarray(logits, np.float32)
        finally:
            self.release(sm)

    def _generate_batch(self, name: str, tokens: np.ndarray,
                        max_new_tokens: int, version: str
                        ) -> Tuple[np.ndarray, RequestStats]:
        t_start = time.perf_counter()
        sm, load_s = self.load_model(name, version)
        B, S = tokens.shape
        max_len = S + max_new_tokens
        exe_p, c1, sig_p = self._executable(sm.cfg, "prefill", B, S, max_len)
        exe_d, c2, sig_d = self._executable(sm.cfg, "decode", B, 1, max_len)
        extra_c = 0.0
        req = spans.request_id()

        t0 = time.perf_counter()
        with spans.span("engine.prefill", req=req, S=S):
            batch = _prefill_batch(sm.cfg, tokens)
            pkey = None
            hit = None
            if self.prefix_kv is not None:
                from repro.serving.prefix_cache import prompt_key
                pkey = prompt_key(name, tokens, max_len)
                hit = self.prefix_kv.lookup(pkey)
            if hit is not None:
                logits, cache = hit  # immutable jax arrays: zero-copy share
            else:
                (logits, cache), dc = self._run_exe(sig_p, exe_p, sm.params,
                                                    batch)
                extra_c += dc
                if self.prefix_kv is not None:
                    self.prefix_kv.insert(pkey, logits, cache,
                                          time.perf_counter() - t0)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            jax.block_until_ready(tok)
            ttft_s = time.perf_counter() - t_start
        out = [tok]
        row = None
        if (B == 1 and max_new_tokens > 1 and sm.cfg.family == DENSE
                and not sm.cfg.n_experts):
            extra_c += self._build_row_programs(sm.cfg, sm.params, cache, tok,
                                                max_len)
            row = _Row(sm.key, arch_signature(sm.cfg), max_len, sm.params,
                       (sig_d, exe_d), cache, S, max_new_tokens - 1, out)
            cache = None    # the row's steps free each cache they replace
        with spans.span("engine.decode", req=req,
                        steps=max_new_tokens - 1) as sp:
            counts = []     # MoE steps' routing counts, left on the device
            if row is not None:
                extra_c += self._decode_row(row, req)
            else:
                for i in range(max_new_tokens - 1):
                    with spans.span("engine.step", req=req, rows=1):
                        (logits, cache, *c), dc = self._run_exe(
                            sig_d, exe_d, sm.params, cache, tok,
                            jnp.int32(S + i))
                    extra_c += dc
                    counts += c
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    out.append(tok)
            result = np.asarray(jnp.stack(out, axis=1))
            if counts and spans.tracing():      # one read, after the loop
                sp.set_metadata(**_moe_stats(
                    sm.cfg, B, np.stack(jax.device_get(counts))))
        compute_s = max(0.0, time.perf_counter() - t0 - extra_c)

        tm = sm.loaded.timings
        st = RequestStats(
            model=name, cold=not sm.loaded.via_trims or tm.tier_hit != "device",
            tier_hit=tm.tier_hit, model_load_s=load_s,
            compile_s=c1 + c2 + extra_c, compute_s=compute_s,
            total_s=time.perf_counter() - t_start,
            modeled_load_s=tm.modeled_total(), ttft_s=ttft_s)
        self.release(sm)
        return result, st

    # ------------------------------------------------------ decode rows
    def _build_row_programs(self, cfg: ModelConfig, params, cache, tok,
                            max_len: int) -> float:
        """The first time a B=1 dense request decodes at ``max_len`` for its
        architecture, build the two-row decode programs that pair it with
        each length already seen there, itself included, so that no row
        compiles when it joins another. Returns the seconds it took."""
        arch = arch_signature(cfg)
        with self._lock:
            if (arch, max_len) in self._row_specs:
                return 0.0
            self._row_specs[(arch, max_len)] = _abstract((cache, tok))
            lens = sorted(n for a, n in self._row_specs if a == arch)
        t0 = time.perf_counter()
        p_spec = _abstract(params)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        exes, lowered = {}, []
        for n in lens:
            a, b = sorted((max_len, n))
            (ca, ta), (cb, tb) = (self._row_specs[(arch, a)],
                                  self._row_specs[(arch, b)])
            exes[(arch, a, b)] = exe = jax.jit(
                lambda p, c, t, i: _rows_step(cfg, p, c, t, i))
            lowered.append(exe.lower(p_spec, (ca, cb), (ta, tb), (pos, pos)))
        # compiled in parallel; each fills its jit's cache for these shapes
        with ThreadPoolExecutor(len(lowered)) as pool:
            list(pool.map(lambda lo: lo.compile(), lowered))
        with self._lock:
            self._row_exe.update(exes)
        return time.perf_counter() - t0

    def _decode_row(self, row: _Row, req) -> float:
        """Decode ``row`` to its last token beside the other rows of its
        model. At each step boundary this thread launches its row's next
        step, together with one more row of the same model when their
        two-row program is built and no other thread is launching that row;
        else alone, with the B=1 program. A row's step waits for the token
        of its step before last, so at most two are in flight and a row
        that finishes prefill joins within a step. Returns the seconds the
        row's first B=1 steps spent compiling."""
        cv = self._rows_cv
        compile_s = 0.0
        with cv:
            self._decoding.setdefault(row.key, []).append(row)
        try:
            while True:
                with cv:
                    while row.busy:
                        cv.wait()
                    if row.error is not None:
                        raise row.error
                    if row.left == 0:
                        return compile_s
                    seen = len(row.out)
                if seen >= 2:   # pace, free for another thread to take
                    jax.block_until_ready(row.out[seen - 2])
                with cv:
                    if row.busy or len(row.out) != seen:
                        continue            # stepped by another thread
                    rows = [row] + self._partner(row)
                    for r in rows:
                        r.busy = True
                try:
                    compile_s += self._step_rows(rows, row.params, req)
                except Exception as e:
                    for r in rows:
                        r.error = e
                    raise
                finally:
                    with cv:
                        for r in rows:
                            r.busy = False
                        cv.notify_all()
        finally:
            with cv:
                group = self._decoding[row.key]
                group.remove(row)
                if not group:
                    del self._decoding[row.key]

    def _partner(self, row: _Row) -> List[_Row]:
        """Another row of ``row``'s model free to step with it: none busy,
        steps left, and a built program for the pair. Under ``_rows_cv``."""
        for other in self._decoding[row.key]:
            if (other is row or other.busy or other.left == 0
                    or other.error is not None):
                continue
            if (row.arch, *sorted((row.max_len, other.max_len))) \
                    in self._row_exe:
                return [other]
        return []

    def _step_rows(self, rows: List[_Row], params, req) -> float:
        """Launch one step of ``rows`` (held busy by this thread): the B=1
        program for one row, the two-row program for two. Returns the
        seconds a first B=1 call spent compiling."""
        for r in rows:
            if len(r.out) >= 2:
                jax.block_until_ready(r.out[-2])
        dc = 0.0
        with spans.span("engine.step", req=req, rows=len(rows)):
            if len(rows) == 1:
                (r,) = rows
                (logits, r.cache), dc = self._run_exe(
                    *r.solo, r.params, r.cache, r.out[-1], jnp.int32(r.pos))
                toks = (jnp.argmax(logits, axis=-1).astype(jnp.int32),)
            else:
                rows = sorted(rows, key=lambda r: r.max_len)
                exe = self._row_exe[(rows[0].arch,
                                     *(r.max_len for r in rows))]
                toks, caches = exe(params, tuple(r.cache for r in rows),
                                   tuple(r.out[-1] for r in rows),
                                   tuple(np.int32(r.pos) for r in rows))
                for r, c in zip(rows, caches):
                    r.cache = c
        for r, t in zip(rows, toks):
            r.out.append(t)
            r.pos += 1
            r.left -= 1
        return dc

    def _generate_streaming(self, name: str, tokens: np.ndarray,
                            max_new_tokens: int, version: str
                            ) -> Optional[Tuple[np.ndarray, RequestStats]]:
        """Layer-streaming serve (DESIGN.md §9): open the model through
        :meth:`MRM.open_stream`, start prefill as soon as the stem and
        layer-0 windows are resident, and chase the stream layer by layer.
        MoE expert windows of the NEXT layer are demanded while the current
        layer computes. Returns None to fall back to the batch path (warm
        hit, unsupported family, or no layer plan)."""
        t_start = time.perf_counter()
        key = ModelKey(FRAMEWORK, name, version)
        cfg = self._cfg_cache.get((name, version))
        if cfg is None and self.disk.contains(key):
            cfg = self._config_for(key)
        if cfg is None and not self._fetchable(key):
            return None
        if cfg is not None and not _streamable(cfg):
            return None
        from repro.core.cache import Tier
        if self.mrm.resident(key, Tier.DEVICE) or \
                self.mrm.resident(key, Tier.HOST):
            return None            # warm model: batch path is strictly better

        fut = self.mrm.open_stream(key)
        blocked_s = 0.0
        t0 = time.perf_counter()
        fut.wait_prefix(1)          # stem (+ layer 0) landing / plan known
        blocked_s += time.perf_counter() - t0
        if cfg is None:             # cloud-only model: config rides the meta
            raw = (fut.meta or {}).get("config")
            if raw is not None:
                cfg = ModelConfig(**dict(raw))
                self._cfg_cache[(name, version)] = cfg
        if fut.plan is None or cfg is None or not _streamable(cfg):
            # warm hit / non-streaming primary / unknown config: batch path
            # (the close below just drops our reference; bytes stay cached)
            h = fut.result()
            if h is not None:
                self.mrm.close(h)
            return None

        plan = fut.plan
        # windows needed before layer i can run: every window up to and
        # including layer i's last (expert windows follow their base window)
        n_layers = cfg.n_layers
        per_layer_prefix = [0] * n_layers
        expert_windows: Dict[int, List[int]] = {}
        for w in plan:
            if w.layer_index >= 0 and w.layer_index < n_layers:
                per_layer_prefix[w.layer_index] = max(
                    per_layer_prefix[w.layer_index], w.index + 1)
                if w.group == "expert":
                    expert_windows.setdefault(w.layer_index, []).append(w.index)
        if any(p == 0 for p in per_layer_prefix):
            h = fut.result()
            if h is not None:
                self.mrm.close(h)
            return None

        B, S = tokens.shape
        max_len = S + max_new_tokens
        exe_e, c1, sig_e = self._executable(cfg, "sembed", B, S, max_len)
        exe_l, c2, sig_l = self._executable(cfg, "slayer", B, S, max_len)
        exe_g, c3, sig_g = self._executable(cfg, "slogits", B, S, max_len)
        exe_de, c4, sig_de = self._executable(cfg, "sdembed", B, 1, max_len)
        exe_dl, c5, sig_dl = self._executable(cfg, "sdlayer", B, 1, max_len)
        trace_s = c1 + c2 + c3 + c4 + c5
        extra_c = 0.0

        template = jax.eval_shape(
            lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
        stem_tpl = {k: v for k, v in template.items() if k != "layers"}
        conv = jnp.asarray

        def stem_params():
            flat = {n: a for n, a in fut.arrays.items()
                    if not n.startswith("layers/")}
            return flat_to_params_like(stem_tpl, flat, convert=conv)

        def layer_params(i):
            flat = {n[len("layers/"):]: fut.arrays[n][i]
                    for n in fut.arrays if n.startswith("layers/")}
            return flat_to_params_like(template["layers"], flat, convert=conv)

        t_c0 = time.perf_counter()
        tw = time.perf_counter()
        fut.wait_prefix(per_layer_prefix[0])
        blocked_s += time.perf_counter() - tw
        stem = stem_params()
        positions = jnp.arange(S)[None, :]
        x, dc = self._run_exe(sig_e, exe_e, stem, jnp.asarray(tokens, jnp.int32))
        extra_c += dc
        layers: List[Any] = []
        caches: List[Any] = []
        for i in range(n_layers):
            tw = time.perf_counter()
            fut.wait_prefix(per_layer_prefix[i])
            blocked_s += time.perf_counter() - tw
            layers.append(layer_params(i))
            for wi in expert_windows.get(i + 1, ()):   # overlap next layer's
                fut.demand(wi)                         # expert bank with math
            (x, cl), dc = self._run_exe(sig_l, exe_l, layers[i], x, positions)
            extra_c += dc
            caches.append(cl)
        logits, dc = self._run_exe(sig_g, exe_g, stem, x)
        extra_c += dc
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(tok)
        ttft_s = time.perf_counter() - t_start
        out = [tok]
        for step in range(max_new_tokens - 1):
            pos = jnp.int32(S + step)
            x, dc = self._run_exe(sig_de, exe_de, stem, tok)
            extra_c += dc
            for i in range(n_layers):
                (x, caches[i]), dc = self._run_exe(
                    sig_dl, exe_dl, layers[i], x, caches[i], pos)
                extra_c += dc
            logits, dc = self._run_exe(sig_g, exe_g, stem, x)
            extra_c += dc
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        result = np.asarray(jnp.stack(out, axis=1))
        compute_s = max(0.0, time.perf_counter() - t_c0 - extra_c)

        h = fut.result()            # loader done (verifies all windows)
        tm = fut.timings
        st = RequestStats(
            model=name, cold=True, tier_hit=tm.tier_hit,
            model_load_s=blocked_s,   # critical-path wait, not wall staging
            compile_s=trace_s + extra_c, compute_s=compute_s,
            total_s=time.perf_counter() - t_start,
            modeled_load_s=tm.modeled_total(), ttft_s=ttft_s, streamed=True)
        if h is not None:
            self.mrm.close(h)
        return result, st


# ---------------------------------------------------------------------------
# request queue + batching (workload-modeling harness, paper Fig. 11)
# ---------------------------------------------------------------------------

@dataclass
class Request:
    model: str
    tokens: np.ndarray
    max_new: int = 4
    done: Optional[threading.Event] = None
    result: Any = None
    stats: Optional[RequestStats] = None
    id: int = -1                  # numbered by ServingWorkers.submit
    queued: Any = None            # its serving.queue span, until taken


class ServingWorkers:
    """N concurrent workers draining a shared queue — the paper's
    'concurrency level'."""

    def __init__(self, engine: InferenceEngine, n_workers: int = 4,
                 lookahead_prefetch: bool = True, lookahead: int = 1):
        self.engine = engine
        self.n_workers = n_workers
        self.lookahead_prefetch = lookahead_prefetch
        self.lookahead = max(1, lookahead)   # distinct queued models to warm
        import queue as _q
        self.q: "_q.Queue[Optional[Request]]" = _q.Queue()
        self.threads = [threading.Thread(target=self._run, daemon=True)
                        for _ in range(n_workers)]
        for t in self.threads:
            t.start()

    def submit(self, req: Request) -> Request:
        req.done = threading.Event()
        req.id = spans.next_request_id()
        # entered here, exited by the worker that takes the request
        req.queued = spans.span("serving.queue", req=req.id)
        req.queued.__enter__()
        self.q.put(req)
        return req

    def _peek_next_models(self, n: int) -> List[str]:
        """First ``n`` DISTINCT models in the queue (no dequeue) — the
        prefetch targets. Deduped so a burst of requests for one model
        costs one hint."""
        out: List[str] = []
        seen = set()
        with self.q.mutex:
            for item in self.q.queue:
                if item is None or item.model in seen:
                    continue
                seen.add(item.model)
                out.append(item.model)
                if len(out) >= n:
                    break
        return out

    def _peek_next_model(self) -> Optional[str]:
        """Model of the next queued request (no dequeue) — prefetch target."""
        nxt = self._peek_next_models(1)
        return nxt[0] if nxt else None

    def _prefetch_next(self, req: Request) -> int:
        """Hint the MRM toward the models queued behind ``req``; returns
        the number of hints issued."""
        eng = self.engine
        hints = 0
        for nxt in self._peek_next_models(self.lookahead):
            if nxt == req.model:
                continue
            if eng.use_trims:
                from repro.core.cache import Tier
                k = ModelKey(FRAMEWORK, nxt, "1")
                if eng.mrm.resident(k, Tier.DEVICE):
                    continue   # already staged: the hint is free work
            # overlap the NEXT requests' model staging with THIS
            # request's load+compute (async MRM load, zero refs);
            # with streaming on, non-disk-resident targets warm
            # through a partial open (layer hints ride along)
            eng.prefetch(nxt)
            hints += 1
        return hints

    def _run(self):
        while True:
            req = self.q.get()
            if req is None:
                return
            req.queued.__exit__(None, None, None)
            req.queued = None
            if self.lookahead_prefetch:
                with spans.span("serving.lookahead", req=req.id) as sp:
                    sp.set_metadata(hints=self._prefetch_next(req))
            try:
                with spans.request(req.id):
                    req.result, req.stats = self.engine.generate(
                        req.model, req.tokens, req.max_new)
            except Exception as e:  # noqa: BLE001
                req.result = e
            finally:
                req.done.set()

    def drain(self, reqs: List[Request], timeout: float = 600.0):
        for r in reqs:
            r.done.wait(timeout)

    def stop(self):
        for _ in self.threads:
            self.q.put(None)
        for t in self.threads:
            t.join(timeout=5)
