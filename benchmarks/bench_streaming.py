"""Layer-granular streaming staging: TTFT vs reassemble-then-run.

Two halves (DESIGN.md §9):

* **Modeled sweep** — model depth x wire bandwidth, on the DEFAULT
  :class:`HardwareModel` constants. The baseline is what the system does
  without streaming: pull the file over the wire to disk, then the serial
  staging chain (disk re-read + deserialize + H2D) and the full prefill.
  Streaming scatters each layer window off the wire directly and runs its
  slice of prefill behind it (``streaming_ttfl_time``). In-bench asserts:
  streaming never loses, wins strictly in every wire-dominated cell, and
  is >= 1.5x at the slow-link corner (250 MB/s — a congested disk-class
  link, half the modeled local-disk rate).
* **Mechanism run** — a real ObjectStore published with
  ``shard_plan="layers"`` served by a streaming ``InferenceEngine``
  against the batch engine on the same weights, asserting byte-identical
  ``generate()`` tokens (dense + MoE).

``--smoke`` shrinks both for the CI gate (scripts/ci.sh --fast).
"""
from __future__ import annotations

import os
import shutil
import tempfile

import numpy as np

from repro.core.costmodel import HardwareModel, streaming_ttfl_time

from benchmarks.common import MRM_COMPUTE_EFF, write_csv

# sweep geometry: a transformer's stem (embedding + head) and per-layer
# trunk bytes; depth scales the trunk only
STEM_BYTES = 512 << 20
LAYER_BYTES = 256 << 20
PREFILL_TOKENS = 2048
SLOW_LINK_BW = 250e6          # the slow-link corner of the sweep


def _compute_s(nbytes: int, hw: HardwareModel) -> float:
    """Modeled prefill seconds for a window's weights: one matmul pass per
    token, 2 flops per (bf16) weight byte, at the serving efficiency."""
    return PREFILL_TOKENS * nbytes / (MRM_COMPUTE_EFF * hw.peak_flops)


def model_cell(depth: int, wire_bw: float, hw: HardwareModel) -> dict:
    windows = [STEM_BYTES] + [LAYER_BYTES] * depth
    nb = sum(windows)
    compute = [_compute_s(n, hw) for n in windows]

    wire_s = nb / wire_bw
    base_ttft = (wire_s + hw.staging_serial_time(nb) + sum(compute))
    post = [n / hw.ingest_bw + n / hw.h2d_bw + c
            for n, c in zip(windows, compute)]
    ttfl, done = streaming_ttfl_time([n / wire_bw for n in windows], post)
    stream_ttft = done[-1]

    stage_totals = {
        "wire_s": wire_s,
        "disk_s": hw.disk_time(nb),
        "deserialize_s": hw.deserialize_time(nb),
        "h2d_s": hw.h2d_time(nb),
        "compute_s": sum(compute),
    }
    wire_dominated = all(wire_s >= v for k, v in stage_totals.items()
                         if k != "wire_s")
    return {
        "depth": depth, "wire_bw": wire_bw, "nbytes": nb,
        **stage_totals,
        "ttfl_s": ttfl,                  # stem+layer0 ready: prefill starts
        "stream_ttft_s": stream_ttft,
        "base_ttft_s": base_ttft,
        "speedup": base_ttft / stream_ttft,
        "wire_dominated": wire_dominated,
    }


def run_modeled(depths, bandwidths, verbose: bool = True):
    hw = HardwareModel()              # DEFAULT constants, not measure()
    rows = []
    for depth in depths:
        for bw in bandwidths:
            r = model_cell(depth, bw, hw)
            rows.append(r)
            if verbose:
                print(f"  L={depth:3d} bw={bw/1e6:7.0f}MB/s  "
                      f"base={r['base_ttft_s']:8.2f}s  "
                      f"stream={r['stream_ttft_s']:8.2f}s  "
                      f"ttfl={r['ttfl_s']:6.2f}s  "
                      f"{r['speedup']:5.2f}x"
                      f"{'  [wire-dom]' if r['wire_dominated'] else ''}")
    # -- in-bench acceptance ------------------------------------------------
    for r in rows:
        assert r["stream_ttft_s"] <= r["base_ttft_s"] * 1.0001, r
        if r["wire_dominated"]:
            assert r["speedup"] > 1.0, (
                "streaming must win every wire-dominated cell", r)
    slow = [r for r in rows if r["wire_bw"] == SLOW_LINK_BW]
    if slow:
        corner = max(slow, key=lambda r: r["depth"])
        assert corner["speedup"] >= 1.5, (
            "slow-link corner must be >= 1.5x", corner)
        if verbose:
            print(f"  slow-link corner (L={corner['depth']}, 250 MB/s): "
                  f"{corner['speedup']:.2f}x")
    return rows


def run_mechanism(root: str, verbose: bool = True) -> list:
    """Real shard_plan="layers" store + streaming engine vs batch engine:
    same tokens, earlier first token, byte-identical output."""
    import jax

    from repro.configs import get_config
    from repro.core.mrm import MRM
    from repro.core.objectstore import ObjectStore
    from repro.core.store import DiskStore
    from repro.models.model import init_params
    from repro.serving.engine import InferenceEngine, publish_model

    rows = []
    for arch in ("olmo-1b", "qwen3-moe-30b-a3b"):
        cfg = get_config(arch).reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        d_ref = DiskStore(os.path.join(root, arch, "ref"))
        key = publish_model(d_ref, cfg, params, name=arch)
        eng_ref = InferenceEngine(d_ref, MRM(d_ref))

        store = ObjectStore(os.path.join(root, arch, "obj"))
        store.put_file(key, d_ref.path_for(key), shard_plan="layers",
                       shard_bytes=64 * 1024)
        d_cold = DiskStore(os.path.join(root, arch, "cold"))
        eng_s = InferenceEngine(d_cold, MRM(d_cold, objectstore=store),
                                streaming=True)

        toks = (np.arange(8, dtype=np.int32).reshape(1, 8)) % cfg.vocab_size
        out_ref, st_ref = eng_ref.generate(arch, toks, max_new_tokens=4)
        out_s, st_s = eng_s.generate(arch, toks, max_new_tokens=4)
        assert st_s.streamed, f"{arch}: cold cloud load must stream"
        assert np.array_equal(out_ref, out_s), (
            f"{arch}: streamed tokens differ from batch path")
        rows.append({"arch": arch, "streamed": st_s.streamed,
                     "ttft_stream_s": st_s.ttft_s,
                     "ttft_batch_s": st_ref.ttft_s,
                     "identical": True})
        if verbose:
            print(f"  {arch}: byte-identical, streamed ttft={st_s.ttft_s:.3f}s"
                  f" (batch warm-path ttft={st_ref.ttft_s:.3f}s)")
    return rows


def run(smoke: bool = False, verbose: bool = True):
    depths = [4, 16, 80] if smoke else [4, 8, 16, 32, 64, 80]
    bandwidths = ([SLOW_LINK_BW, 1e9, 10e9] if smoke
                  else [SLOW_LINK_BW, 500e6, 1e9, 2e9, 10e9])
    if verbose:
        print("-- modeled TTFT sweep: depth x wire bandwidth --")
    rows = run_modeled(depths, bandwidths, verbose=verbose)

    root = tempfile.mkdtemp(prefix="bench-streaming-")
    try:
        if verbose:
            print("-- mechanism: layer-planned store, streamed generate --")
        mech = run_mechanism(root, verbose=verbose)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    write_csv("streaming_ttfl", rows + mech)
    return rows, mech


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep + tiny models for the CI gate")
    args = ap.parse_args()
    run(smoke=args.smoke)
    print("bench_streaming: OK")
