"""Closed-loop arrivals: ``clients`` callers, each sending its next request
as soon as its previous one has been answered."""
from __future__ import annotations

LOOP = "closed"


def clients(params: dict) -> int:
    return int(params["clients"])
