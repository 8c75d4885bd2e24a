"""Observability layer: tracing spans, round metrics, and the JSONL run
ledger (DESIGN.md section 11).

* ``obs.trace`` — host-side spans with ``block_until_ready`` fencing and
  a cold/warm split for jitted entry points; an enabled span is also a
  ``jax.profiler`` host event, on the device trace's clock.
* ``obs.metrics`` — the shared AoU bucket edges and ``json_safe`` (the
  one JSON scrubbing rule).
* ``obs.ledger`` — per-run manifest + JSONL event stream under
  ``experiments/runs/`` (gate: ``REPRO_LEDGER``).
"""
from . import ledger, metrics, trace
from .ledger import RunLedger
from .metrics import AOU_BUCKET_EDGES, aou_histogram, json_safe
from .trace import Span, Tracer, span, tracing

__all__ = [
    "trace", "metrics", "ledger",
    "Span", "Tracer", "span", "tracing",
    "AOU_BUCKET_EDGES", "aou_histogram", "json_safe",
    "RunLedger",
]
