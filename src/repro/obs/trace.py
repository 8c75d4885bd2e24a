"""Host-side tracing spans: where does a round's wall-clock go?

The observability layer's timing substrate (DESIGN.md section 11). A
``Span`` is one timed region of host code — a planner stage, an engine
dispatch, a benchmark rep — recorded on a monotonic clock
(``time.perf_counter``) with explicit nesting. Four contracts matter for
JAX code:

* **fencing** — an XLA dispatch returns before the computation finishes,
  so a span that closes without synchronizing measures dispatch latency,
  not work. ``handle.fence(arrays)`` registers outputs to
  ``jax.block_until_ready`` at span exit, making the duration honest.
* **compile-vs-execute split** — the first call of a jitted entry point
  pays tracing + XLA compilation on top of execution. Spans carry a
  ``cold`` flag (``Tracer.cold(key)`` marks the first sighting of a
  static signature) so reports can separate amortized-away compile time
  from steady-state execution.
* **one clock** — an enabled span also enters
  ``jax.profiler.TraceAnnotation(name, **meta)``: under a running
  ``jax.profiler`` trace every span is a host-plane event on the same
  clock as the device's operations, its scalar meta (and late
  ``note(...)`` values) as the event's arguments, tuples as strings.
  With no profiler running the annotation costs about a microsecond.
* **zero cost when disabled** — the global tracer is OFF by default and
  the disabled ``span`` is a shared no-op context (no generator, no
  allocation, no ``jax`` import), so production paths keep their
  instrumentation permanently.

Usage::

    from repro.obs import trace
    with trace.tracing() as tr:
        with trace.span("engine.schedule_batch") as sp:
            out = eng.schedule_batch(...)
            sp.fence(out.t_round)
    print(trace.format_report(tr.summarize()))

``profile(outdir)`` is the opt-in ``jax.profiler.trace`` hook (surfaced
through ``launch/perf.py --profile``) for when host spans are not enough
and the full XLA timeline is needed; it raises when the profiler cannot
start.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import numbers
import time
from typing import Any, Optional

__all__ = [
    "Span", "Tracer", "tracing", "span", "note", "get_tracer",
    "set_tracer", "profile", "summarize", "format_report",
]


@dataclasses.dataclass
class Span:
    """One closed timed region (monotonic-clock seconds)."""
    name: str
    t_start: float            # perf_counter() at entry
    duration_s: float         # fenced: includes block_until_ready
    depth: int                # nesting depth (0 = top level)
    parent: Optional[str]     # name of the enclosing span, None at top
    cold: bool                # first call of a jitted signature
    meta: dict                # caller-attached key/values

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class _Handle:
    """The object a live ``span(...)`` yields: attach fences + metadata."""
    __slots__ = ("_fences", "meta", "_late")

    def __init__(self, meta: dict):
        self._fences: list = []
        self.meta = meta
        self._late: dict = {}     # notes made after entry

    def fence(self, *arrays) -> None:
        """Register arrays/pytrees to ``jax.block_until_ready`` at exit."""
        self._fences.extend(arrays)

    def note(self, **meta) -> None:
        self.meta.update(meta)
        self._late.update(meta)


def _event_args(meta: dict) -> dict:
    """A span's meta as profiler event arguments: real numbers and strings
    as they are, tuples as strings; ``cold`` and other values are left
    out."""
    return {k: str(v) if isinstance(v, tuple) else v
            for k, v in meta.items()
            if k != "cold" and isinstance(v, (numbers.Real, str, tuple))}


class _NullHandle:
    """Shared no-op handle for the disabled tracer."""
    __slots__ = ()

    def fence(self, *arrays) -> None:
        pass

    def note(self, **meta) -> None:
        pass


_NULL_HANDLE = _NullHandle()


class _NullCtx:
    """Shared no-op context manager (no allocation per disabled span)."""
    __slots__ = ()

    def __enter__(self):
        return _NULL_HANDLE

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


@functools.lru_cache(maxsize=None)
def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on the first enabled
    span (a disabled tracer never imports ``jax``)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


class _SpanCtx:
    """Live span context manager (plain class — cheaper than a
    ``@contextmanager`` generator on hot paths)."""
    __slots__ = ("_tracer", "_name", "_cold", "_handle", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cold: bool, meta: dict):
        self._tracer = tracer
        self._name = name
        self._cold = cold
        self._handle = _Handle(meta)

    def __enter__(self):
        self._tracer._stack.append(self)
        self._ann = _annotation()(self._name,
                                  **_event_args(self._handle.meta))
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self._handle

    def __exit__(self, *exc):
        h = self._handle
        if h._fences:
            import jax
            jax.block_until_ready(h._fences)
        dt = time.perf_counter() - self._t0
        late = _event_args(h._late)
        if late:
            self._ann.set_metadata(**late)
        self._ann.__exit__(*exc)
        tr = self._tracer
        tr._stack.pop()
        depth = len(tr._stack)
        parent = tr._stack[-1]._name if tr._stack else None
        # a late note(cold=...) overrides the entry-time flag — for spans
        # whose static signature is only known mid-region (e.g. mc_loop
        # sees its (S, N) shape after the first env_fn call)
        cold = bool(h.meta.pop("cold", self._cold))
        tr.spans.append(Span(name=self._name, t_start=self._t0,
                             duration_s=dt, depth=depth, parent=parent,
                             cold=cold, meta=h.meta))
        return False


class Tracer:
    """Span collector. ``enabled=False`` makes every ``span`` a shared
    no-op; re-enable any time. Not thread-safe by design (one tracer per
    driver thread — the engines dispatch from a single host thread)."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.spans: list[Span] = []
        self._stack: list[_SpanCtx] = []    # the open spans, innermost last
        self._seen: set = set()

    def span(self, name: str, *, cold: Optional[bool] = None, **meta):
        if not self.enabled:
            return _NULL_CTX
        return _SpanCtx(self, name, bool(cold), meta)

    def note(self, **meta) -> None:
        """``note(**meta)`` on the innermost open span, if one is open."""
        if self._stack:
            self._stack[-1]._handle.note(**meta)

    def cold(self, key: Any) -> bool:
        """True exactly once per ``key`` — mark a jitted entry point's
        first call with a static signature (compile happens there)."""
        if key in self._seen:
            return False
        self._seen.add(key)
        return True

    def clear(self) -> None:
        self.spans.clear()
        self._stack.clear()

    def summarize(self) -> list[dict]:
        return summarize(self.spans)


# -- global tracer -----------------------------------------------------------

_TRACER = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    global _TRACER
    old, _TRACER = _TRACER, tracer
    return old


def span(name: str, *, cold: Optional[bool] = None, **meta):
    """Open a span on the global tracer (no-op context when disabled)."""
    return _TRACER.span(name, cold=cold, **meta)


def note(**meta) -> None:
    """``Tracer.note`` on the global tracer: meta for the innermost open
    span, from code that does not hold its handle (no-op when disabled)."""
    if _TRACER.enabled:
        _TRACER.note(**meta)


def cold(key: Any) -> bool:
    """``Tracer.cold`` on the global tracer (always False when disabled —
    disabled runs track no compile-cache state)."""
    return _TRACER.enabled and _TRACER.cold(key)


@contextlib.contextmanager
def tracing(enabled: bool = True):
    """Swap in a fresh enabled tracer for the block; restores the previous
    one on exit. Yields the new tracer (read ``.spans`` / ``.summarize()``
    after the block's work)."""
    old = set_tracer(Tracer(enabled=enabled))
    try:
        yield get_tracer()
    finally:
        set_tracer(old)


# -- profiler ------------------------------------------------------------


@contextlib.contextmanager
def profile(outdir: str):
    """Opt-in ``jax.profiler.trace`` hook: dump an XLA/TensorBoard profile
    of the block to ``outdir`` (view with ``tensorboard --logdir``).
    Raises what the profiler raises when it cannot start (one already
    running, no profiler on this backend)."""
    import jax

    with jax.profiler.trace(outdir):
        yield


# -- reporting ---------------------------------------------------------------


def summarize(spans: list[Span]) -> list[dict]:
    """Aggregate spans per name: call count, total/mean/max seconds, and
    the cold (first-call, compile-inclusive) vs warm split. Ordered by
    total descending."""
    agg: dict[str, dict] = {}
    for s in spans:
        a = agg.setdefault(s.name, {
            "name": s.name, "count": 0, "total_s": 0.0, "max_s": 0.0,
            "cold_count": 0, "cold_s": 0.0, "warm_s": 0.0,
        })
        a["count"] += 1
        a["total_s"] += s.duration_s
        a["max_s"] = max(a["max_s"], s.duration_s)
        if s.cold:
            a["cold_count"] += 1
            a["cold_s"] += s.duration_s
        else:
            a["warm_s"] += s.duration_s
    out = []
    for a in agg.values():
        warm_n = a["count"] - a["cold_count"]
        a["mean_s"] = a["total_s"] / a["count"]
        a["warm_mean_s"] = a["warm_s"] / warm_n if warm_n else None
        out.append(a)
    out.sort(key=lambda a: -a["total_s"])
    return out


def format_report(summary: list[dict]) -> str:
    """Fixed-width table of a ``summarize()`` result."""
    lines = [f"{'span':36s} {'calls':>6s} {'total':>10s} {'mean':>10s} "
             f"{'warm mean':>10s} {'cold':>10s}"]
    for a in summary:
        wm = a["warm_mean_s"]
        lines.append(
            f"{a['name'][:36]:36s} {a['count']:>6d} "
            f"{a['total_s'] * 1e3:>8.2f}ms {a['mean_s'] * 1e3:>8.2f}ms "
            f"{(wm * 1e3 if wm is not None else float('nan')):>8.2f}ms "
            f"{a['cold_s'] * 1e3:>8.2f}ms")
    return "\n".join(lines)
