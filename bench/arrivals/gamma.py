"""Open-loop arrivals with gamma-distributed gaps.

The coefficient of variation ``cv`` sets the burstiness: 1 is a Poisson
process, 2 has the bursts of the Azure Functions characterization (Shahrad
et al., ATC '20). Every seed gets the same set of gaps, drawn once from
``draw_seed`` and scaled so that ``round(rate_per_s * seconds)`` requests
fall due in ``[0, seconds)`` at exactly the mean rate, in the order that
``rng`` (seeded from the traffic's ``order_seed``) draws.
"""
from __future__ import annotations

import numpy as np

LOOP = "open"


def schedule(params: dict, seconds: float, rng: np.random.Generator) -> list:
    n = max(1, round(params["rate_per_s"] * seconds))
    shape = 1.0 / params["cv"] ** 2
    gaps = np.random.default_rng(params["draw_seed"]).gamma(shape, 1.0, n)
    gaps = rng.permutation(gaps * (seconds / gaps.sum()))
    return [float(t) for t in np.concatenate([[0.0], np.cumsum(gaps)[:-1]])]
