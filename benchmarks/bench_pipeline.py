"""Eviction-demotion ablation (DESIGN.md §2).

A device tier that fits two models rotating through three. With
eviction-as-demotion the loser of each eviction lands in the host tier, so
reloads are host hits; with drop-on-evict every reload goes back to disk.
"""
from __future__ import annotations

from benchmarks.common import BenchEnv, write_csv
from repro.core import MRM, ModelKey


def run_demotion_ablation(env: BenchEnv, n_rounds: int = 4, verbose=True,
                          policy: str = "lru"):
    """Three similar-size models, device AND host tiers each fit two.

    Rotating A,B,C forces host evictions of models still device-resident;
    when that device copy is later evicted, demotion re-homes it in HOST
    (next open = host hit) while drop-on-evict pays a full disk reload.
    ``policy`` selects the eviction policy — bench_slo's parity check runs
    this non-oversubscribed rotation under lru AND slo."""
    names = ["ResNet50", "ResNet50-v2", "ResNeXt50"]
    size = max(env.specs[n].mwmf_bytes for n in names)
    rows = []
    for demote in (False, True):
        mrm = MRM(env.disk, device_capacity=int(size * 2.5),
                  host_capacity=int(size * 2.5), hw=env.hw,
                  demote_on_evict=demote, policy=policy)
        vclock = [0.0]
        if mrm.slo is not None:
            # seed-audit fix (bench_slo technique): the slo predictor's
            # recency signal must come from the modeled timeline, not
            # host wall time — otherwise eviction decisions (and the
            # lru/slo parity gate) vary with host speed and break A/B
            # trace comparability
            mrm.slo.predictor.clock = lambda: vclock[0]
        tier_hits = []
        for _ in range(n_rounds):
            for name in names:
                h = mrm.open(ModelKey("repro-jax", name, "1"))
                tier_hits.append(h.timings.tier_hit)
                mrm.close(h)
                vclock[0] += h.timings.modeled_total()
        stats = mrm.stats()
        rows.append({"demote_on_evict": demote, "policy": policy,
                     "tier_hits": tier_hits,
                     "disk_loads": stats["disk_loads"],
                     "demotions": stats["demotions"]})
        if verbose:
            print(f"  demote={demote!s:<5} disk_loads={stats['disk_loads']:2d} "
                  f"demotions={stats['demotions']:2d} "
                  f"host_hits={tier_hits.count('host'):2d}")
    write_csv("demotion_ablation", rows)
    return rows


def run(env: BenchEnv | None = None, verbose=True):
    env = env or BenchEnv()
    if verbose:
        print("-- eviction-as-demotion vs drop --")
    rows = run_demotion_ablation(env, verbose=verbose)
    with_d = next(r for r in rows if r["demote_on_evict"])
    without = next(r for r in rows if not r["demote_on_evict"])
    if verbose:
        saved = without["disk_loads"] - with_d["disk_loads"]
        print(f"  demotion saved {saved} disk reloads")
    return {"demotion": rows}


if __name__ == "__main__":
    run()
