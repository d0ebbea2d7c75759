"""MRM tiers: share of the window's opens that hit the device tier, from the
change of ``mrm.stats()`` across the window."""


def read(run):
    before, after = run.window.mrm_before, run.window.mrm_after
    opens = after["opens"] - before["opens"]
    hits = after["device"]["hits"] - before["device"]["hits"]
    return 100.0 * hits / opens if opens else None
