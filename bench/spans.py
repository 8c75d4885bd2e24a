"""The program's spans and the device's name scopes in a chip trace, and
the per-layer numbers they give.

    python bench/spans.py --workload <cell> --seed <n> [--seconds <s>]

An enabled ``obs.trace`` span is a host-plane event of the profile of its
own, with its counts as arguments, on the device's clock. This module reads
those events (``load``) and, where the device's ops carry their name-scope
path (``SCOPE_STAT``, kept on each op's event metadata, which
``ProfileData`` does not show), each op by that path. ``reduce`` gives, for
each span name in the ``bench.window``, its time (the union of its
intervals), count, argument sums and the device-idle time inside it, and
the device time per name scope; ``NUMBERS`` turns those into per-layer
numbers.

``bench/trace_reduce.py`` and the harness are left as they are: the
harness's per-layer readers see only ``trace_reduce.Reduction``, which
keeps no span arguments and no scope paths, and the harness deletes its
trace before the readers run. So these numbers are not metrics of
``BENCHMARK.json``. Run as a script, this module makes one traced run of a
cell through the harness's own ``run`` (same set-up, window and check),
reads the same trace file as the harness does, and prints the harness's
result line, then one JSON line of these numbers.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce as T  # noqa: E402

SCOPE_STAT = "tf_op"     # an XLA op's name-scope path, ``a/b/.../<op>``


@dataclasses.dataclass
class Event(T.Event):
    args: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Spans:
    # per name of the profile's own host events (the program's spans among
    # them) starting in the window: seconds of the union of its intervals,
    # count, the sum of each numeric argument, and the seconds inside that
    # union in which device 0 ran nothing
    span_s: dict
    span_n: dict
    span_args: dict
    span_idle_s: dict
    # name scope -> device seconds of the union of the ops under it
    # (averaged over devices)
    scope_s: dict


# -- reading the file -----------------------------------------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of one protobuf message in ``buf[i:end]``:
    an int for a varint, ``(start, end)`` for a length-delimited field."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def _plane_scopes(buf, plane) -> dict:
    """``{event name: its SCOPE_STAT}`` of one ``XPlane``
    (``buf[plane[0]:plane[1]]``)."""
    stat_names, metas = {}, []
    for f, v in _fields(buf, *plane):
        if f in (4, 5):                       # event_metadata, stat_metadata
            entry = dict(_fields(buf, *v))    # map entry: 1 key, 2 value
            if f == 4 and 2 in entry:
                metas.append(entry[2])
            elif f == 5 and 2 in entry:
                m = dict(_fields(buf, *entry[2]))
                if 2 in m:
                    stat_names[entry[1]] = _text(buf, m[2])
    out = {}
    for meta in metas:
        name = scope = None
        for f, v in _fields(buf, *meta):
            if f == 2:
                name = _text(buf, v)
            elif f == 5:                      # XStat: 1 metadata_id,
                st = dict(_fields(buf, *v))   # 5 str_value, 7 ref_value
                if stat_names.get(st.get(1)) == SCOPE_STAT:
                    scope = (_text(buf, st[5]) if 5 in st
                             else stat_names.get(st.get(7)))
        if name is not None and scope:
            out[name] = scope
    return out


def scope_paths(path: str) -> dict:
    """``{plane name: {event name: name-scope path}}`` of the device
    planes of an ``.xplane.pb`` file."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f == 1:                            # XSpace.planes
            name = next((_text(buf, v) for g, v in _fields(buf, *plane)
                         if g == 2), "")
            if name.startswith(T.DEVICE_PREFIX):
                out[name] = _plane_scopes(buf, plane)
    return out


def load(path: str):
    """(native, scoped) of an ``.xplane.pb`` file: the host plane's own
    events with their arguments, and per device the ops that carry a
    name-scope path, named by that path."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    scopes = scope_paths(path)
    native, scoped = [], []
    for plane in pd.planes:
        if plane.name.startswith(T.DEVICE_PREFIX):
            op_scope = scopes.get(plane.name, {})
            scoped.append([
                T.Event(op_scope[e.name], float(e.start_ns),
                        float(e.duration_ns))
                for line in plane.lines if line.name == T.OP_LINE
                for e in line.events if e.name in op_scope])
        elif plane.name == T.HOST_PLANE:
            native.extend(Event(e.name, float(e.start_ns),
                                float(e.duration_ns), dict(e.stats))
                          for line in plane.lines for e in line.events
                          if e.duration_ns > 0)
    return native, scoped


# -- the reduction ----------------------------------------------------------
def covered(busy, intervals) -> float:
    """Length of ``intervals`` that the merged, sorted ``busy`` covers."""
    starts = [s for s, _ in busy]
    before = [0.0]
    for s, e in busy:
        before.append(before[-1] + e - s)

    def upto(t):
        i = bisect.bisect_right(starts, t) - 1
        return before[i] + min(t, busy[i][1]) - busy[i][0] if i >= 0 else 0.0

    return sum(upto(e) - upto(s) for s, e in intervals)


def reduce_spans(events, t0_ns, t1_ns, busy):
    """(seconds, count, argument sums, idle seconds) per name of the
    ``events`` that start in [t0, t1); seconds are of the union of a
    name's intervals (clipped at t1), idle seconds the part of that union
    ``busy`` leaves uncovered."""
    ivs, n, args = {}, {}, {}
    for e in events:
        if not t0_ns <= e.start_ns < t1_ns:
            continue
        ivs.setdefault(e.name, []).append((e.start_ns, min(e.end_ns, t1_ns)))
        n[e.name] = n.get(e.name, 0) + 1
        sums = args.setdefault(e.name, {})
        for k, v in e.args.items():
            if isinstance(v, (int, float)):
                sums[k] = sums.get(k, 0) + v
    secs, idle = {}, {}
    for name, iv in ivs.items():
        u = T.union(iv)
        total = sum(e - s for s, e in u)
        secs[name] = total / 1e9
        idle[name] = (total - covered(busy, u)) / 1e9
    return secs, n, args, idle


def scope_time(ops, t0_ns, t1_ns) -> dict:
    """Nanoseconds per name scope: the union of the intervals of the ops
    (named by their scope path) that start in [t0, t1) under it."""
    ivs = {}
    for e in ops:
        if t0_ns <= e.start_ns < t1_ns:
            for scope in set(e.name.split("/")[:-1]):
                ivs.setdefault(scope, []).append(
                    (e.start_ns, min(e.end_ns, t1_ns)))
    return {k: sum(e - s for s, e in T.union(iv)) for k, iv in ivs.items()}


def reduce(tr: T.Trace, native, scoped) -> Spans:
    """The spans and scopes of ``load``'s (native, scoped) within the
    window of ``tr``, the same file read by ``trace_reduce.load``."""
    t0_ns, t1_ns = T.window_bounds(tr)
    busy0 = T.union(T.clip([(e.start_ns, e.end_ns) for e in tr.ops[0]],
                           t0_ns, t1_ns))
    span_s, span_n, span_args, span_idle_s = reduce_spans(
        native, t0_ns, t1_ns, busy0)
    scope_s = {}
    for dev in scoped:
        for k, v in scope_time(dev, t0_ns, t1_ns).items():
            scope_s[k] = scope_s.get(k, 0.0) + v * 1e-9 / len(tr.ops)
    return Spans(span_s=span_s, span_n=span_n, span_args=span_args,
                 span_idle_s=span_idle_s, scope_s=scope_s)


# -- the numbers ------------------------------------------------------------
def sgd_step_idle_ms(sp: Spans, red: T.Reduction):
    """Device-idle time inside the cohort's local training (the
    ``server.train`` spans of ``fl/server.py`` ``run_round``) per SGD step
    dispatched (their ``steps`` argument), in ms."""
    steps = sp.span_args.get("server.train", {}).get("steps")
    return 1e3 * sp.span_idle_s["server.train"] / steps if steps else None


def fl_aggregate_ms(sp: Spans, red: T.Reduction):
    """Time of a round's aggregation (``server.aggregate``: the stack of
    the cohort's deltas, the weighted sum and the update of the
    parameters, fenced), in ms per round."""
    n = sp.span_n.get("server.aggregate")
    return 1e3 * sp.span_s["server.aggregate"] / n if n else None


def fl_select_ms(sp: Spans, red: T.Reduction):
    """Time of a round's wireless step and client selection
    (``server.scenario`` and ``server.select``), in ms per round."""
    n = sp.span_n.get("server.select")
    if not n:
        return None
    return 1e3 * (sp.span_s["server.select"]
                  + sp.span_s.get("server.scenario", 0.0)) / n


def mc_entry_host_ms(sp: Spans, red: T.Reduction):
    """Host time of a ``run_montecarlo`` call outside the fenced round
    loop (``mc.call`` less ``engine.mc_loop``: engine and scenario set-up,
    the pulls to the host and the summaries), in ms per call."""
    n = sp.span_n.get("mc.call")
    if not n:
        return None
    return 1e3 * (sp.span_s["mc.call"]
                  - sp.span_s.get("engine.mc_loop", 0.0)) / n


def mc_admit_device_ms(sp: Spans, red: T.Reduction):
    """Device time under the name scope ``mc.admit`` (``core/engine.py``
    ``_montecarlo_step``'s admission stage) per execution of that step, in
    ms."""
    s = sp.scope_s.get("mc.admit")
    _, n = red.module_time(r"^jit__montecarlo_step$")
    return 1e3 * s / n if s is not None and n else None


NUMBERS = (sgd_step_idle_ms, fl_aggregate_ms, fl_select_ms,
           mc_entry_host_ms, mc_admit_device_ms)

# parent span -> the child spans that should cover it
PARTS = {"server.round": ("server.scenario", "server.select",
                          "server.train", "server.aggregate"),
         "mc.call": ("mc.setup", "engine.mc_loop", "mc.collect")}


def numbers(sp: Spans, red: T.Reduction) -> dict:
    """The per-layer numbers that read something here, and the share of
    each parent span of ``PARTS`` that its children cover."""
    out = {}
    for fn in NUMBERS:
        v = fn(sp, red)
        if v is not None:
            out[fn.__name__] = v
    for parent, parts in PARTS.items():
        if sp.span_s.get(parent):
            out[f"covered.{parent}"] = sum(
                sp.span_s.get(p, 0.0) for p in parts) / sp.span_s[parent]
    return out


def main():
    import argparse
    import json
    from bench import run as harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    a = ap.parse_args()
    seen = {}
    load_trace = T.load

    def load_both(path):   # the harness's reading of its file, and ours
        tr = load_trace(path)
        seen["spans"] = reduce(tr, *load(path))
        seen["red"] = T.reduce(tr)
        return tr

    T.load = load_both
    try:
        result = harness.run(a.workload, a.seed, a.seconds, True)
    finally:
        T.load = load_trace
    print(json.dumps(result, allow_nan=False), flush=True)
    sp = seen["spans"]
    print(json.dumps({"workload": a.workload, "seed": a.seed,
                      "numbers": numbers(sp, seen["red"]),
                      "span_s": sp.span_s, "span_n": sp.span_n,
                      "span_args": sp.span_args,
                      "span_idle_s": sp.span_idle_s,
                      "scope_s": sp.scope_s}), flush=True)


if __name__ == "__main__":
    main()
