#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, as the only process using the chip(s). The
cell is looked up in ``BENCHMARK.json``; its configuration, traffic mix and
per-layer metrics are the files ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py``, the
configuration's architecture is ``bench/arch/<bench_arch>.py`` and the
traffic's arrival kind is ``bench/arrivals/<kind>.py``. Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared for ``correct``
beside its limit. The same checks are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: dict, kind: str) -> List[dict]:
    """The cell's end-to-end (``kind="end_to_end"``) or per-layer metrics."""
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


@dataclass
class RunView:
    """What a per-layer metric's reader may read."""
    cell: dict
    model: dict
    traffic: dict
    peak: dict
    window: object                 # harness.Window
    by_index: dict                 # request index -> mix.Spec
    trace: object = None           # trace.Trace, with --trace 1
    trace_window: Optional[tuple] = None
    arch: object = None            # the configuration's bench/arch module


def read_metric(name: str, view: RunView) -> Optional[float]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


def checks_of(limits: Dict[str, float], values: Dict[str, float]) -> Dict[str, dict]:
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, peak: dict,
             log: Callable[[str], None], t_start: float,
             after_window: Optional[Callable] = None,
             keep_trace: Optional[Path] = None) -> dict:
    """Set up, measure, check; return the result line as a dict.
    ``after_window(cell, window)`` runs once the window has closed (tests
    plant faults with it); ``keep_trace`` receives a copy of the trace."""
    import jax

    from bench import harness, trace as T

    dev = jax.devices()[0]
    cell_obj = harness.Cell(config, traffic, seed, log=log)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        cell_obj.setup()
        setup_s = time.perf_counter() - t_start
        log(f"set-up done in {setup_s:.3f} s; device peak so far "
            f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')} bytes")
        win = cell_obj.run_window(seconds, trace_dir=trace_dir)
        desc = harness.describe(win)
        log(f"window: {json.dumps(desc)}")
        stats = dev.memory_stats() or {}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
        if after_window is not None:
            after_window(cell_obj, win)
        tiers = {"device"} | ({"host"} if "host" in desc["tiers"] else set())
        weights_off = cell_obj.check_weights(sorted(tiers))
        done = [s for s in win.requests if s.error is None and s.stats is not None]
        picked = cell_obj.sample(done, int(traffic["sample"]))
        cell_obj.release()
        t_ref = time.perf_counter()
        gaps = cell_obj.logit_gaps(picked)
        log(f"reference over {len(picked)} requests, {gaps['tokens']} served "
            f"tokens, took {time.perf_counter() - t_ref:.3f} s")
        failed = sum(1 for s in win.requests if s.error is not None)
        for s in win.requests:
            if s.error is not None:
                log(f"request {s.index} failed: {s.error}")
        limits = {"logit_gap": config["check"]["logit_gap_limit"],
                  "weights_off": 0, "failed_requests": 0,
                  "compiles_in_window": 0}
        values = {"logit_gap": gaps["gap"] if picked else float("inf"),
                  "weights_off": weights_off, "failed_requests": failed,
                  "compiles_in_window": win.compiles}
        checks = checks_of(limits, values)
        correct = all(c["value"] <= c["limit"] for c in checks.values())

        result = {"correct": correct, "attempted": len(win.requests),
                  "failed": failed, "metrics": {}, "device": device}
        if not trace:
            e2e = harness.end_to_end(win)
            e2e["setup_s"] = setup_s
            for m in cell_metrics(bench, cell, "end_to_end"):
                if e2e.get(m["name"]) is not None:
                    result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                    "unit": m["unit"]}
        else:
            t_tr = time.perf_counter()
            files = list(Path(trace_dir).rglob("*.xplane.pb"))
            tr = T.load(str(files[0]))
            if keep_trace is not None:
                import shutil
                shutil.copy(files[0], keep_trace)
            lo, hi = T.window(tr)
            view = RunView(cell, config["model"], traffic, peak, win,
                           {s.index: s for s in win.requests}, tr, (lo, hi),
                           cell_obj.arch)
            for m in cell_metrics(bench, cell, "per_layer"):
                v = read_metric(m["name"], view)
                if v is not None:
                    result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
            busy = T.length(T.busy(tr, lo, hi)) * 1e-9
            device["busy_s"] = busy
            device["window_s"] = (hi - lo) * 1e-9
            result["breakdown"] = {"device_ops": T.top_ops(tr, lo, hi),
                                   "idle_gaps": T.idle_gaps(tr, lo, hi)}
            log(f"trace of {files[0].stat().st_size} bytes read in "
                f"{time.perf_counter() - t_tr:.3f} s")
        result["checks"] = checks
        return result
    finally:
        cell_obj.close()
        if trace_dir is not None:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    # the compile cache lives at a fixed path inside this checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from repro.launch.serve import enable_compile_cache
    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devs = jax.devices()
    dev = devs[0]
    tag = f"[{dev.platform} {dev.device_kind} x{len(devs)}]"

    def log(msg: str) -> None:
        print(f"{tag} {msg}", file=sys.stderr, flush=True)

    if dev.platform != "tpu" or len(devs) < int(cell["chips"]):
        log(f"needs {cell['chips']} TPU chip(s); JAX found {len(devs)} "
            f"{dev.platform} device(s). No result.")
        return 2
    peaks = load_json(BENCH / "peaks.json")
    if dev.device_kind not in peaks:
        log(f"no peaks for device kind {dev.device_kind!r} in bench/peaks.json")
        return 2

    result = run_cell(bench, cell, config, traffic, args.seed, args.seconds,
                      bool(args.trace), peaks[dev.device_kind], log, T_START)
    for m, v in result["metrics"].items():
        log(f"metric {m}: {v['value']!r} {v['unit']}")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
