#!/usr/bin/env python3
"""Record the small trace the trace-reduction tests read, on the chip.

    python3 bench/tests/record_trace.py <cell> <out.xplane.pb> [seconds]

Runs the cell with tracing on for a short window and keeps its
``.xplane.pb``; prints the result line.
"""
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

if __name__ == "__main__":
    t0 = time.perf_counter()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = run.load_json(ROOT / "BENCHMARK.json")
    cell = run.find_cell(bench, sys.argv[1])
    seconds = float(sys.argv[3]) if len(sys.argv) > 3 else 1.0
    peaks = run.load_json(run.BENCH / "peaks.json")
    res = run.run_cell(
        bench, cell, run.load_json(run.BENCH / "configs" / f"{cell['config']}.json"),
        run.load_json(run.BENCH / "traffic" / f"{cell['traffic']}.json"),
        int(time.time()), seconds, True, peaks[jax.devices()[0].device_kind],
        lambda m: print(m, file=sys.stderr), t0, keep_trace=Path(sys.argv[2]))
    print(json.dumps(res))
