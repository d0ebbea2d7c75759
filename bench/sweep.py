#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate whose backlog does
not grow over a window.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 20 \\
        --rates 1,2,3,4,5

Sets the cell up once and runs one window per rate, in the order given. One
JSON line per rate on standard output: the end-to-end metrics and the
window's counts (requests sent, answered, finished inside the window, and
the backlog still unanswered at the close). A rate is sustained on a seed
when the backlog at the close stays within the number of workers; bursts
make that noisy, so run several seeds and take as the knee the highest rate
sustained on every seed, with every lower rate. The benchmark's own runs use
the fixed rate in the traffic file; this only informs it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness
    from bench.run import BENCH, find_cell, load_json
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    c = harness.Cell(config, traffic, args.seed,
                     log=lambda m: print(m, file=sys.stderr, flush=True))
    try:
        c.setup()
        for rate in (float(r) for r in args.rates.split(",")):
            print(json.dumps(sweep_line(c, rate, args.seconds)), flush=True)
    finally:
        c.close()
    return 0


def sweep_line(c, rate: float, seconds: float) -> dict:
    """One window of a set-up cell at ``rate``: its metrics and counts."""
    from bench import harness

    win = c.run_window(seconds, rate_per_s=rate)
    desc = harness.describe(win)
    workers = int(c.traffic["serving"]["workers"])
    return {"rate_per_s": rate, **harness.end_to_end(win), **desc,
            "sustained": desc["backlog_at_close"] <= workers}


if __name__ == "__main__":
    sys.exit(main())
