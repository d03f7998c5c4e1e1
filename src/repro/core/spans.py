"""Spans: the program's one timer, on the profiler's clock.

:func:`span` wraps a piece of work in a ``jax.profiler.TraceAnnotation``,
so under an active profiler session it lands on the calling thread's host
line beside the device events, on the same clock.  Outside a session an
annotation costs about a microsecond.  Given a ``seconds`` dict, the span
also writes its wall time there, which is how ``stage_seconds``,
``TCResult.preprocess_seconds`` / ``count_seconds`` and
``ManyResult.plan_seconds`` / ``count_seconds`` are filled.

Every span inside one count carries that count's id (``count_id``,
opened by :func:`count_scope`), so spans of one request can be grouped
even when serving threads interleave.  The names, outermost first:

* ``tc.count`` — one call of ``count_triangles`` / ``_many`` / ``_delta``;
* ``tc.plan`` — host work before the engine call: plan-cache lookup,
  planning on a miss, staging;
* ``tc.plan.digest`` — the graph's content digest, on every call;
  ``memo=1`` where the digest kept on the ``Graph`` was returned
  (every graph of a batch), ``memo=0`` where edges were hashed;
* ``tc.plan.relabel`` / ``.hubsplit`` / ``.rebalance`` / ``.pack`` /
  ``.delta`` — planning stages, on a plan-cache miss;
* ``tc.stage`` — host→device upload of the plan arrays;
* ``tc.dispatch`` — the engine call (trace and compile, or cache load,
  on its first call);
* ``tc.wait`` — waiting for the device's result;
* ``tc.fetch`` — the result's device→host transfer.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import time
from typing import Callable, Dict, Optional

import jax

__all__ = ["span", "count_scope", "launch"]

_COUNT_ID: contextvars.ContextVar = contextvars.ContextVar(
    "tc_count_id", default=None
)
_NEXT_ID = itertools.count(1)


@contextlib.contextmanager
def span(
    name: str, seconds: Optional[Dict] = None, key: Optional[str] = None,
    **meta,
):
    """Annotate the enclosed work as ``name``, with ``meta`` and the
    count's ``count_id`` as the annotation's metadata; with ``seconds``,
    also write its wall time into ``seconds[key or name]``, exceptions
    included."""
    cid = _COUNT_ID.get()
    if cid is not None:
        meta["count_id"] = cid
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **meta):
            yield
    finally:
        if seconds is not None:
            seconds[key or name] = time.perf_counter() - t0


@contextlib.contextmanager
def count_scope():
    """One ``tc.count`` span with a fresh ``count_id``; inside an open
    count (a delta count's inner ``count_triangles``) it adds nothing, so
    one request keeps one id."""
    if _COUNT_ID.get() is not None:
        yield
        return
    token = _COUNT_ID.set(next(_NEXT_ID))
    try:
        with span("tc.count"):
            yield
    finally:
        _COUNT_ID.reset(token)


def launch(fn: Callable, staged: Dict, seconds: Dict, fetch: Callable = int):
    """Run the engine ``fn`` on ``staged`` and return ``fetch`` of its
    result, as ``tc.dispatch``, ``tc.wait`` and ``tc.fetch``; their wall
    times go to ``seconds`` under ``dispatch``, ``wait`` and ``fetch``."""
    with span("tc.dispatch", seconds, "dispatch"):
        out = fn(**staged)
    with span("tc.wait", seconds, "wait"):
        jax.block_until_ready(out)
    with span("tc.fetch", seconds, "fetch"):
        return fetch(out)
