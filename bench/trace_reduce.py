"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: the device's busy union and idle gaps over the
traced window, device time per XLA module and per op, and the host span
open during each idle gap.

Device planes are named ``/device:TPU:<i>``; their ``XLA Modules`` line has
one event per execution of a compiled program (``jit_<name>(<id>)``) and
their ``XLA Ops`` line one event per operation, Mosaic kernels included.
Host spans are the events of the host plane's lines: the harness's own
``TraceAnnotation``s and the program's ``obs.trace`` spans, which the
harness puts on the profiler's clock (``host_spans`` below).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    modules: list      # per device: [Event]
    ops: list          # per device: [Event] named <module>/<op>
    host: list         # [Event], every host-side span


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules, ops, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            mods, dev_ops = [], []
            for line in plane.lines:
                if line.name not in (MODULE_LINE, OP_LINE):
                    continue
                dest, name = ((mods, module_base) if line.name == MODULE_LINE
                              else (dev_ops, op_base))
                dest.extend(Event(name(e.name), float(e.start_ns),
                                  float(e.duration_ns))
                            for e in line.events)
            modules.append(mods)
            ops.append(attribute(dev_ops, mods))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(Event(e.name, float(e.start_ns),
                                  float(e.duration_ns))
                            for e in line.events if e.duration_ns > 0)
    if not ops or not any(ops):
        raise ValueError(f"{path}: no device operations in the trace")
    return Trace(modules=modules, ops=ops, host=host)


def op_base(name: str) -> str:
    """``%fusion.6 = f32[...] fusion(...)`` -> ``fusion.6``."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_base(name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                # busy union, averaged over devices
    module_s: dict               # module name -> seconds (averaged over devices)
    module_n: dict               # module name -> executions (per device)
    op_s: dict                   # <module>/<op> -> seconds (averaged)
    gaps: list                   # [(seconds, host span name)] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, pattern: str):
        """(seconds, executions) of the modules whose base name matches
        ``pattern`` (a regular expression searched in the name)."""
        rx = re.compile(pattern)
        keys = [k for k in self.module_s if rx.search(k)]
        return (sum(self.module_s[k] for k in keys),
                sum(self.module_n[k] for k in keys))


def window_bounds(tr: Trace):
    """The harness's ``bench.window`` annotation, else the device span."""
    spans = [e for e in tr.host if e.name == WINDOW_SPAN]
    if spans:
        w = spans[0]
        return w.start_ns, w.end_ns
    allops = [e for dev in tr.ops for e in dev]
    return (min(e.start_ns for e in allops), max(e.end_ns for e in allops))


def innermost_span(host, t_ns):
    """Name of the shortest host span that covers ``t_ns``."""
    best = None
    for e in host:
        if e.start_ns <= t_ns <= e.end_ns and (best is None
                                               or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else "(no host span)"


def attribute(ops, modules):
    """Each op as ``<module>/<op>``: the module execution whose interval
    holds the op's start (op names repeat across modules)."""
    mods = sorted(modules, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in mods]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start_ns) - 1
        owner = (mods[i].name if i >= 0 and e.start_ns < mods[i].end_ns
                 else "(no module)")
        out.append(Event(f"{owner}/{e.name}", e.start_ns, e.dur_ns))
    return out


def reduce(tr: Trace, n_gaps: int = 10) -> Reduction:
    t0_ns, t1_ns = window_bounds(tr)
    n_dev = len(tr.ops)
    busy = 0.0
    module_s, module_n, op_s = {}, {}, {}
    gaps = []
    for d in range(n_dev):
        u = union(clip([(e.start_ns, e.end_ns) for e in tr.ops[d]],
                       t0_ns, t1_ns))
        busy += sum(e - s for s, e in u)
        edges = [t0_ns] + [x for iv in u for x in iv] + [t1_ns]
        if d == 0:
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, (s + e) / 2))
        for e in tr.modules[d]:
            if t0_ns <= e.start_ns < t1_ns:
                module_s[e.name] = module_s.get(e.name, 0.0) + e.dur_ns
                module_n[e.name] = module_n.get(e.name, 0) + 1
        for e in tr.ops[d]:
            if t0_ns <= e.start_ns < t1_ns:
                op_s[e.name] = op_s.get(e.name, 0.0) + e.dur_ns
    gaps.sort(reverse=True)
    named = [(g / 1e9, innermost_span(tr.host, mid))
             for g, mid in gaps[:n_gaps]]
    scale = 1e-9 / n_dev
    return Reduction(
        window_s=(t1_ns - t0_ns) / 1e9, busy_s=busy * scale,
        module_s={k: v * scale for k, v in module_s.items()},
        module_n={k: v // n_dev for k, v in module_n.items()},
        op_s={k: v * scale for k, v in op_s.items()}, gaps=named)


def breakdown(red: Reduction, n: int = 10) -> dict:
    """The result line's ``breakdown``: top device ops by time and the
    longest idle gaps by the host span open in them."""
    top = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[name, s] for s, name in red.gaps[:n]]}
