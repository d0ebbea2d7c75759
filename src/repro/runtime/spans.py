"""Trace spans of the serving path, on the profiler's clock.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation`` while a
profiler session records, so it lands in the same ``.xplane.pb`` as the
device planes; otherwise it is a shared no-op. Spans of one request carry
its number as the ``req`` stat, bound with :func:`request`. JAX is never
imported here: a process that has not imported it runs no profiler session.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import sys
from typing import Iterator, Optional

_request: contextvars.ContextVar = contextvars.ContextVar("request",
                                                          default=None)
_ids = itertools.count()


class _Off:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        pass


_OFF = _Off()


def tracing() -> bool:
    """Whether a profiler session is recording spans."""
    prof = sys.modules.get("jax.profiler")
    return prof is not None and prof.TraceAnnotation.is_enabled()


def span(name: str, **stats):
    """A span named ``name`` with ``stats``; ``set_metadata`` adds stats
    known only at its end."""
    if not tracing():
        return _OFF
    return sys.modules["jax.profiler"].TraceAnnotation(name, **stats)


def next_request_id() -> int:
    return next(_ids)


def request_id() -> Optional[int]:
    return _request.get()


@contextlib.contextmanager
def request(req_id: Optional[int] = None) -> Iterator[int]:
    """Bind ``req_id`` to the spans opened inside; without one, keep the id
    already bound, else take the next number."""
    if req_id is None:
        req_id = _request.get()
        if req_id is None:
            req_id = next_request_id()
    token = _request.set(req_id)
    try:
        yield req_id
    finally:
        _request.reset(token)
