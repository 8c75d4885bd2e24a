"""Benchmark harness: runs one cell of ``BENCHMARK.json`` once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the cell
(``bench/cells/<cell>.json``: its configuration, traffic mix and the limits
of its comparison), the configuration (``bench/configs/<config>.json``),
the traffic mix (``bench/traffic/<mix>.json``, which names the driver of
the entry point it exercises, ``bench/drivers/<driver>.py``) and each
metric, end-to-end or per-layer (``bench/metrics/<metric>.py``).

A run: set-up (imports, data and weights from the seed, compilation or
the persistent cache, warm-up at the cell's own shapes), then a closed loop
of calls or rounds for ``--seconds``; then the peak device memory is read,
the driver closes (it drives what its comparison needs beyond the window
and frees the program's state) and the plain reference checks what the
program produced. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` profiles the first units of the window and reports its
per-layer metrics from the device trace. Information goes to earlier
lines; the last line of stdout is one JSON object, whose last key,
``checks``, holds each number compared beside its limit; the same numbers
are the last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        fail(f"missing {path}")


def load_cell(name: str, spec: dict, base: pathlib.Path = BENCH
              ) -> types.SimpleNamespace:
    """The cell ``name`` of ``spec`` (a parsed BENCHMARK.json) with its
    files under ``base``, the end-to-end metrics it reports and its
    per-layer metrics."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        fail(f"unknown workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    cell = load_json(base / "cells" / f"{name}.json")
    if (cell["config"], cell["traffic"]) != (w["config"], w["traffic"]):
        fail(f"bench/cells/{name}.json disagrees with BENCHMARK.json")
    config = load_json(base / "configs" / f"{w['config']}.json")
    traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in names
                                  else [])]
    return types.SimpleNamespace(name=name, chips=w["chips"], cell=cell,
                                 config=config, traffic=traffic, e2e=e2e,
                                 per_layer=per_layer)


def reader_path(metric: str, base: pathlib.Path = BENCH) -> pathlib.Path:
    """The reader of ``metric``: ``metrics/<metric>.py``, or, for a metric
    split by the cells it moves (``<name>.<part>``), ``metrics/<name>.py``;
    under ``base`` first, then under ``bench/``."""
    for d in (base, BENCH):
        for stem in (metric, metric.split(".")[0]):
            path = d / "metrics" / f"{stem}.py"
            if path.exists():
                return path
    fail(f"no reader for the metric {metric!r}")


def reader(metric: str, base: pathlib.Path = BENCH):
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", reader_path(metric, base))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def prepare(chips: int, require_chip: bool = True):
    """Environment of a run: the program on the path, its run ledger off
    (it writes a directory and calls git on every call), the persistent
    compilation cache on; then the devices, which must be TPUs enough."""
    os.environ["REPRO_LEDGER"] = "0"
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    if importlib.util.find_spec("repro") is None:
        fail("the program (src/repro) is not in this checkout")
    from repro.launch import compile_cache
    cache = compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            fail(f"no TPU: JAX's first device is {dev.platform!r} "
                 f"({dev.device_kind})")
        if len(devices) < chips:
            fail(f"the cell asks for {chips} chips, JAX finds "
                 f"{len(devices)}")
    print(f"device: {dev.device_kind} x{len(devices)}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)
    return devices


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        require_chip: bool = True, spec: dict | None = None,
        base: pathlib.Path = BENCH) -> dict:
    """One run of one cell; returns the result object (see module doc)."""
    spec = spec if spec is not None else load_json(ROOT / "BENCHMARK.json")
    c = load_cell(workload, spec, base)
    devices = prepare(c.chips, require_chip)
    dev = devices[0]
    import jax

    compiles = {"n": 0, "on": False}

    def on_compile(event, duration, **kw):
        if compiles["on"] and event == COMPILE_EVENT:
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    ctx = types.SimpleNamespace(config=c.config, traffic=c.traffic,
                                cell=c.cell, seed=seed, name=workload)
    drv = importlib.import_module(
        f"bench.drivers.{c.traffic['driver']}").Driver(ctx)
    drv.setup()
    setup_s = time.perf_counter() - T_START
    print(f"setup_s {setup_s:.3f}", flush=True)

    n_trace = c.traffic["trace_units"] if traced else 0
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    units, traced_units, sync = [], [], None
    compiles["on"] = True
    w0 = time.perf_counter()
    if traced:
        from repro.obs import trace as obs_trace
        obs_trace.set_tracer(obs_trace.Tracer(enabled=True))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.sync"):
            sync = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(n_trace):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench.{drv.unit}"):
                    rec = drv.run_unit()
                rec["wall_s"] = time.perf_counter() - t0
                traced_units.append(rec)
        jax.profiler.stop_trace()
        spans = list(obs_trace.get_tracer().spans)
        obs_trace.set_tracer(obs_trace.Tracer(enabled=False))
        units.extend(traced_units)
    while time.perf_counter() - w0 < seconds:
        t0 = time.perf_counter()
        rec = drv.run_unit()
        rec["wall_s"] = time.perf_counter() - t0
        units.append(rec)
    window_s = time.perf_counter() - w0
    jax.monitoring.unregister_event_duration_listener(on_compile)
    used = devices[:c.chips]
    stats = [d.memory_stats() or {} for d in used]
    peak = max(m.get("peak_bytes_in_use", 0) for m in stats)
    limit = max(m.get("bytes_limit", 0) for m in stats)
    print(f"window_s {window_s:.3f}; {len(units)} {drv.unit}s in the window "
          f"({len(units) / window_s:.3f} per s); compiles in the window "
          f"{compiles['n']}; memory_peak_bytes {peak} of {limit}", flush=True)
    walls = sorted(u["wall_s"] for u in units)
    if walls:
        print(f"{drv.unit} wall_s: min {walls[0]:.4f} median "
              f"{walls[len(walls) // 2]:.4f} max {walls[-1]:.4f}; longest "
              f"{[round(w, 4) for w in walls[-3:]]}", flush=True)

    drv.close()
    from bench import compare
    t_ref = time.perf_counter()
    numbers = drv.numbers()
    print(f"reference check took {time.perf_counter() - t_ref:.3f} s",
          flush=True)
    checks = compare.judge(numbers, c.cell["limits"])
    correct = all(ok for *_, ok in checks)
    info = {k: v for k, v in numbers.items() if k not in c.cell["limits"]}
    print(f"compared: {info}", flush=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    metrics, breakdown = {}, None
    if traced:
        from bench import trace_reduce
        tr = trace_reduce.load(trace_reduce.find_xplane(log_dir))
        sync_ev = [e for e in tr.host if e.name == "bench.sync"]
        if sync_ev:   # put the program's host spans on the trace's clock
            off = sync_ev[0].start_ns - sync * 1e9
            tr.host.extend(trace_reduce.Event(
                s.name, s.t_start * 1e9 + off, s.duration_s * 1e9)
                for s in spans)
        red = trace_reduce.reduce(tr)
        shutil.rmtree(log_dir, ignore_errors=True)
        counts = {}
        for u in traced_units:
            for k, v in u.items():
                counts[k] = counts.get(k, 0) + v
        m = types.SimpleNamespace(reduction=red, counts=counts,
                                  device_kind=dev.device_kind,
                                  config=c.config, traffic=c.traffic)
        for pm in c.per_layer:
            v = reader(pm["name"], base)(m)
            if v is not None:
                metrics[pm["name"]] = {"value": v, "unit": pm["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = trace_reduce.breakdown(red)
    else:
        m = types.SimpleNamespace(units=units, window_s=window_s,
                                  setup_s=setup_s)
        for em in c.e2e:
            metrics[em["name"]] = {"value": reader(em["name"], base)(m),
                                   "unit": em["unit"]}

    result = {"correct": correct, "attempted": len(units),
              "failed": 0 if correct else len(units),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = compare.finite({n: {"value": v, "limit": lim}
                                       for n, v, lim, _ in checks})
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    result = run(a.workload, a.seed, a.seconds, bool(a.trace))
    for name, chk in result["checks"].items():
        ok = float(chk["value"]) <= float(chk["limit"])
        print(f"check {name} {chk['value']!r} limit {chk['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result, allow_nan=False), flush=True)


if __name__ == "__main__":
    main()
