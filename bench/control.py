#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's widest logit gap and
the float8 control's, on many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed it sets the cell up, optionally sweeps open-loop rates on that
set-up as ``bench/sweep.py`` does (``--rates``), runs one window of the
cell's own traffic, and compares the same sample of served requests twice
against the float32 reference: the program's served tokens (the number ``correct`` compares) and
the token the reference computed in float8 puts first at each position (the
control, which has to fail the limit). One JSON line per seed on standard
output. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="",
                    help="open-loop rates to sweep (bench/sweep.py) on each "
                         "seed's set-up before the control's window")
    ap.add_argument("--sweep-seconds", type=float, default=45.0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness
    from bench.run import BENCH, find_cell, load_json
    from bench.sweep import sweep_line
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def log(msg: str) -> None:
        print(f"[{args.workload}] {msg}", file=sys.stderr, flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        c = harness.Cell(config, traffic, seed, log=log)
        try:
            c.setup()
            for rate in (float(r) for r in args.rates.split(",") if r):
                print(json.dumps({"seed": seed, **sweep_line(
                    c, rate, args.sweep_seconds)}), flush=True)
            win = c.run_window(args.seconds)
            done = [s for s in win.requests
                    if s.error is None and s.stats is not None]
            picked = c.sample(done, int(traffic["sample"]))
            c.release()
            gaps = c.logit_gaps(picked, control=True)
        finally:
            c.close()
        print(json.dumps({"seed": seed, "program_gap": gaps["gap"],
                          "control_gap": gaps["control_gap"],
                          "program_flips": gaps["flips"],
                          "control_flips": gaps["control_flips"],
                          "tokens": gaps["tokens"], "requests": len(picked),
                          "sent": len(win.requests),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
