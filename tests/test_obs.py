"""Observability layer (src/repro/obs/): tracer spans, metrics, JSONL run
ledger, engine/planner round diagnostics parity, and the bench-regression
gate. The telemetry CONTRACT lives in DESIGN.md section 11 — these tests
pin it."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import FLConfig, NOMAConfig
from repro.core import RoundEnv, aoi, noma, plan
from repro.core.engine import WirelessEngine
from repro.core.engine import schedule_diag as engine_schedule_diag
from repro.fl.server import History
from repro.obs import (
    AOU_BUCKET_EDGES,
    RunLedger,
    aou_histogram,
    json_safe,
    trace,
)
from repro.obs.ledger import EVENT_KEYS, MANIFEST_KEYS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- tracer

def test_span_nesting_and_parent():
    with trace.tracing() as tr:
        with trace.span("outer"):
            with trace.span("inner", k=1):
                pass
        with trace.span("outer2"):
            pass
    names = [s.name for s in tr.spans]
    assert names == ["inner", "outer", "outer2"]  # post-order append
    by = {s.name: s for s in tr.spans}
    assert by["inner"].parent == "outer" and by["inner"].depth == 1
    assert by["outer"].parent is None and by["outer"].depth == 0
    assert by["inner"].meta == {"k": 1}
    assert all(s.duration_s >= 0 for s in tr.spans)


def test_span_disabled_is_noop():
    # outside a tracing() block the global tracer is disabled: spans
    # record nothing, every span is one shared no-op object, and cold()
    # always says False
    before = list(trace.get_tracer().spans)
    with trace.span("nope") as h:
        h.note(x=1)
        h.fence(np.zeros(3))
    assert list(trace.get_tracer().spans) == before
    assert trace.span("a", r=1) is trace.span("b")
    assert trace.cold(("some", "key")) is False


def test_disabled_span_imports_no_jax():
    code = ("import sys\nfrom repro.obs import trace\n"
            "with trace.span('x', r=1) as h:\n    h.note(y=2)\n"
            "print(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules))")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60, env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert out.stdout.strip() == "False", out.stderr


def _host_events(tmp_path, body) -> dict:
    """name -> [arguments] of the host-plane events of a ``jax.profiler``
    trace of ``body()``."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


def test_enabled_span_is_a_profiler_host_event(tmp_path):
    # scalar meta become the event's arguments, tuples strings, late notes
    # are added at exit; arrays and the cold flag are left out
    def body():
        with trace.tracing():
            with trace.span("obs.outer", r=3, shape=(2, 3), arr=np.zeros(2)):
                with trace.span("obs.inner", cold=False) as sp:
                    sp.note(steps=7, cold=True)
        with trace.span("obs.off", r=1):
            pass
    ev = _host_events(tmp_path, body)
    assert ev["obs.outer"] == [{"r": 3, "shape": "(2, 3)"}]
    assert ev["obs.inner"] == [{"steps": 7}]
    assert "obs.off" not in ev


def test_profile_raises_when_the_profiler_cannot_start(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path / "a"))
    try:
        with pytest.raises(RuntimeError):
            with trace.profile(str(tmp_path / "b")):
                pass
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("predictor", ["none", "stale"])
def test_run_round_spans_each_phase(predictor):
    import collections
    import dataclasses

    from repro.configs import get_config
    from repro.data import TaskConfig
    from repro.fl import FLServer
    cfg = dataclasses.replace(get_config("smollm_135m").reduced(),
                              d_model=32, d_ff=64, vocab_size=32, n_layers=2)
    srv = FLServer(cfg, FLConfig(n_clients=8, local_batch=8, lr=0.2,
                                 samples_per_client=(24, 48), seed=0),
                   NOMAConfig(n_subchannels=2),
                   TaskConfig(vocab_size=32, n_topics=4, seq_len=17, seed=0),
                   predictor=predictor)
    srv.run_round()
    real_step, calls = srv.trainer.step, []

    def step(*args):
        calls.append(1)
        return real_step(*args)

    srv.trainer.step = step
    with trace.tracing() as tr:
        srv.run_round()
    by = collections.defaultdict(list)
    for s in tr.spans:
        by[s.name].append(s)
    assert all(s.meta["r"] == 1 for name, ss in by.items()
               if name.startswith(("server.", "client.")) for s in ss)
    (rnd,) = by["server.round"]
    assert rnd.parent is None
    for name in ("server.scenario", "server.select", "server.train",
                 "server.aggregate"):
        (s,) = by[name]
        assert s.parent == "server.round", name
    (train,), (agg,) = by["server.train"], by["server.aggregate"]
    updates = by["client.update"]
    assert updates and all(u.parent == "server.train" for u in updates)
    assert train.meta["clients"] == len(updates) == agg.meta["clients"]
    assert train.meta["steps"] == len(calls) == sum(u.meta["steps"]
                                                    for u in updates) > 0
    leaf_bytes = sum(x.nbytes for x in jax.tree.leaves(srv.params))
    if predictor == "none":
        assert "server.predict" not in by
        # the running FedAvg: one fold per client, inside server.train
        folds = by["server.fold"]
        assert [f.meta["client"] for f in folds] == [u.meta["client"]
                                                     for u in updates]
        assert all(f.parent == "server.train"
                   and f.meta["bytes"] == leaf_bytes for f in folds)
    else:
        assert "server.fold" not in by
        (pred,) = by["server.predict"]
        assert pred.parent == "server.aggregate"


@pytest.mark.parametrize("backend,agg_impl,impl", [
    ("auto", None, "xla"), ("pallas_interpret", None, "xla"),
    ("auto", "interpret", "interpret")])
def test_aggregate_counts_stacked_bytes(backend, agg_impl, impl):
    """Each ``server.fold`` carries ``stacked_bytes``: 0 on the fused path,
    which every ``kernel_backend`` aggregates with; on the stacked kernel
    path that ``agg_impl`` asks for, the bytes of the deltas it stacked:
    one delta in the first fold, the aggregate and a delta in each other,
    (2C - 1) x the delta bytes a round."""
    import dataclasses

    from repro.configs import get_config
    from repro.data import TaskConfig
    from repro.fl import FLServer
    cfg = dataclasses.replace(get_config("smollm_135m").reduced(),
                              d_model=32, d_ff=64, vocab_size=32, n_layers=2)
    srv = FLServer(cfg, FLConfig(n_clients=8, local_batch=8, lr=0.2,
                                 samples_per_client=(24, 48), seed=0,
                                 kernel_backend=backend),
                   NOMAConfig(n_subchannels=2),
                   TaskConfig(vocab_size=32, n_topics=4, seq_len=17, seed=0),
                   agg_impl=agg_impl)
    assert srv.agg_impl == impl
    with trace.tracing() as tr:
        srv.run_round()
    (agg,) = [s for s in tr.spans if s.name == "server.aggregate"]
    folds = [s for s in tr.spans if s.name == "server.fold"]
    leaf_bytes = sum(x.nbytes for x in jax.tree.leaves(srv.params))
    assert agg.meta["clients"] == len(folds) > 0
    assert sum(f.meta["stacked_bytes"] for f in folds) == (
        0 if impl == "xla" else (2 * len(folds) - 1) * leaf_bytes)


def test_note_reaches_the_innermost_open_span():
    """``trace.note`` adds to the innermost open span, and is a no-op with
    no span open or with tracing off."""
    trace.note(x=1)
    with trace.tracing() as tr:
        trace.note(x=2)
        with trace.span("outer", a=0):
            with trace.span("inner"):
                trace.note(x=3)
            trace.note(a=4)
    by = {s.name: s.meta for s in tr.spans}
    assert by == {"inner": {"x": 3}, "outer": {"a": 4}}


def test_run_montecarlo_spans_each_phase():
    from repro.fl.rounds import run_montecarlo
    with trace.tracing() as tr:
        res = run_montecarlo(n_clients=32, n_seeds=2, rounds=3, seed=5,
                             policies=("age_noma",))
    (call,) = [s for s in tr.spans if s.name == "mc.call"]
    assert call.parent is None and call.meta == {"seed": 5, "drops": 6}
    kids = {s.name: s for s in tr.spans if s.parent == "mc.call"}
    assert set(kids) == {"mc.setup", "engine.mc_loop", "mc.collect"}
    assert all(s.meta["seed"] == 5 for s in kids.values())
    assert kids["mc.collect"].meta["bytes"] == sum(
        v.nbytes for v in res["age_noma"].values())


def test_montecarlo_step_stages_are_named_scopes():
    import re

    import jax.numpy as jnp

    from repro.core.engine import _montecarlo_step
    eng = WirelessEngine(NOMAConfig(), FLConfig())
    s, n = 64, 3000                  # segmented admission in a sub-chunk scan
    n_cand0 = min(eng.prm.slots, n)
    x = jnp.ones((s, n), jnp.float32)
    text = _montecarlo_step.lower(
        x, x, x, jax.random.PRNGKey(0), x, x, jnp.float32(1e6),
        jnp.int32(0), None, prm=eng.prm, gamma=1.0, policy="age_noma",
        t_budget=0.0, n_pairs=(n_cand0 + 1) // 2, n_cand0=n_cand0,
        admission="segmented").as_text(debug_info=True)
    paths = re.findall(r'loc\("([^"]*)"', text)
    for scope in ("mc.priority", "mc.admit", "mc.finish", "mc.ages"):
        assert any(scope in p.split("/") for p in paths), scope


def test_cold_fires_once_per_key():
    with trace.tracing() as tr:
        assert trace.cold(("sig", 1)) is True
        assert trace.cold(("sig", 1)) is False
        assert trace.cold(("sig", 2)) is True
        with trace.span("s", cold=trace.cold(("sig", 1))):
            pass
    assert tr.spans[0].cold is False


def test_span_note_late_cold_override():
    with trace.tracing() as tr:
        with trace.span("s", cold=False) as h:
            h.note(cold=True, extra=7)
    s = tr.spans[0]
    assert s.cold is True
    assert s.meta == {"extra": 7}  # cold consumed, not left in meta


def test_summarize_and_report():
    with trace.tracing() as tr:
        for i in range(3):
            with trace.span("work", cold=(i == 0)):
                pass
    summ = trace.summarize(tr.spans)
    row = next(r for r in summ if r["name"] == "work")
    assert row["count"] == 3 and row["cold_count"] == 1
    assert row["total_s"] == pytest.approx(
        row["cold_s"] + row["warm_s"], rel=1e-9)
    assert "work" in trace.format_report(summ)


# --------------------------------------------------------------- metrics

def test_aou_histogram_buckets():
    ages = np.array([0., 1., 1.5, 2., 3., 9., 100.])
    h = aou_histogram(ages)
    assert h.shape == (len(AOU_BUCKET_EDGES) + 1,)
    assert int(h.sum()) == len(ages)
    # (edge[i-1], edge[i]] convention: age 1.0 lands in bucket 0, 1.5 and
    # 2.0 in bucket 1, 9 in (8, 16], 100 overflows into the last bucket
    assert h.tolist() == [2, 2, 1, 0, 1, 0, 1]


def test_json_safe_round_trips_through_json():
    v = json_safe({"a": np.arange(3), "b": np.float32(1.5),
                   "c": float("nan"), "d": (1, np.int64(2))})
    s = json.dumps(v, allow_nan=False)
    assert json.loads(s) == {"a": [0, 1, 2], "b": 1.5, "c": None,
                             "d": [1, 2]}


# ------------------------------------------------------- history + ledger

def test_history_as_dict_json_round_trip():
    h = History()
    h.accuracy.append(float("nan"))
    h.round_time.append(1.25)
    h.participation = np.array([1.0, 0.0, 2.0])
    d = h.as_dict()
    restored = json.loads(json.dumps(d, allow_nan=False))
    assert restored["accuracy"] == [None]
    assert restored["round_time"] == [1.25]
    assert restored["participation"] == [1.0, 0.0, 2.0]
    assert set(d) == {f.name for f in
                      __import__("dataclasses").fields(History)}


def test_ledger_schema(tmp_path):
    with RunLedger.open("unit_test", {"n": 3}, root=str(tmp_path),
                        enabled=True) as led:
        led.event("round", r=0, t_round=1.5, arr=np.arange(2))
    run_dir = led.run_dir
    assert run_dir is not None
    man = json.load(open(os.path.join(run_dir, "manifest.json")))
    for k in MANIFEST_KEYS:
        assert k in man, k
    assert man["kind"] == "unit_test" and man["config"] == {"n": 3}
    lines = [json.loads(ln) for ln in
             open(os.path.join(run_dir, "events.jsonl"))]
    events = [ln["event"] for ln in lines]
    assert events == ["run_start", "round", "run_end"]
    for ln in lines:
        for k in EVENT_KEYS:
            assert k in ln, k
    assert lines[1]["arr"] == [0, 1]


def test_ledger_disabled_null(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_LEDGER", "0")
    led = RunLedger.open("unit_test", root=str(tmp_path))
    led.event("x")
    led.close()
    assert led.run_dir is None
    assert list(tmp_path.iterdir()) == []


# -------------------------------------------------- round diag parity

def _env_batch(rng, b, n, ncfg):
    d = np.stack([noma.sample_distances(rng, n, ncfg) for _ in range(b)])
    gains = np.stack([noma.sample_gains(rng, d[i], ncfg)
                      for i in range(b)])
    ns = rng.integers(100, 1000, (b, n)).astype(float)
    cpu = rng.uniform(0.5e9, 2e9, (b, n))
    ages = np.stack([aoi.init_ages(n) for _ in range(b)]) + \
        rng.integers(0, 6, (b, n)).astype(float)
    return gains, ns, cpu, ages


def test_schedule_diag_numpy_jax_parity():
    rng = np.random.default_rng(3)
    ncfg, fl = NOMAConfig(), FLConfig()
    b, n = 3, 24
    gains, ns, cpu, ages = _env_batch(rng, b, n, ncfg)
    eng = WirelessEngine(ncfg, fl)
    out = eng.schedule_batch(gains, ns, cpu, ages, fl.model_bits)
    jd = engine_schedule_diag(out, ages)
    for i in range(b):
        env = RoundEnv(gains[i], ns[i], cpu[i], ages[i], fl.model_bits)
        sched = plan.plan_round(env, ncfg, fl,
                                priority=plan.age_score(env, fl))
        nd = plan.schedule_diag(sched, ages[i])
        assert np.asarray(jd["n_selected"])[i] == nd["n_selected"]
        assert np.asarray(jd["t_round"])[i] == pytest.approx(
            nd["t_round"], rel=1e-5)
        assert np.asarray(jd["t_comp_bottleneck"])[i] == pytest.approx(
            nd["t_comp_bottleneck"], rel=1e-4, abs=1e-8)
        assert np.asarray(jd["t_up_bottleneck"])[i] == pytest.approx(
            nd["t_up_bottleneck"], rel=1e-4, abs=1e-8)
        np.testing.assert_array_equal(np.asarray(jd["aou_hist"])[i],
                                      nd["aou_hist"])


def test_diag_decomposition_sums_to_t_round():
    # the headline contract: bottleneck t_comp + t_up == t_round, exactly
    # in the fp64 numpy planner, to fp32 tolerance in the engine
    rng = np.random.default_rng(7)
    ncfg, fl = NOMAConfig(), FLConfig()
    env = RoundEnv(noma.sample_gains(
        rng, noma.sample_distances(rng, 20, ncfg), ncfg),
        rng.integers(100, 1000, 20).astype(float),
        rng.uniform(0.5e9, 2e9, 20), aoi.init_ages(20), 4e6)
    d = plan.schedule_diag(plan.plan_round(
        env, ncfg, fl, priority=plan.age_score(env, fl)))
    assert d["t_comp_bottleneck"] + d["t_up_bottleneck"] == pytest.approx(
        d["t_round"], abs=1e-12)


def test_planner_spans_and_joint_diag():
    rng = np.random.default_rng(11)
    ncfg = NOMAConfig(n_subchannels=4)
    fl = FLConfig(selection="joint")
    env = RoundEnv(noma.sample_gains(
        rng, noma.sample_distances(rng, 16, ncfg), ncfg),
        rng.integers(100, 1000, 16).astype(float),
        rng.uniform(0.5e9, 2e9, 16), aoi.init_ages(16), 4e6)
    with trace.tracing() as tr:
        sched = plan.plan_round(env, ncfg, fl,
                                priority=plan.age_score(env, fl))
    names = {s.name for s in tr.spans}
    assert {"plan.admit", "plan.joint", "plan.finalize"} <= names
    assert sched.info["joint_swaps_accepted"] >= 0
    assert isinstance(sched.info["joint_kept"], bool)


def test_mc_loop_diag_keys_and_identity():
    rng = np.random.default_rng(5)
    ncfg, fl = NOMAConfig(), FLConfig()
    r_, s_, n_ = 4, 2, 16
    d = np.stack([[noma.sample_distances(rng, n_, ncfg)
                   for _ in range(s_)] for _ in range(r_)])
    gains_seq = np.stack([[noma.sample_gains(rng, d[r][s], ncfg)
                           for s in range(s_)] for r in range(r_)])
    ns = rng.integers(100, 1000, (s_, n_)).astype(float)
    cpu = rng.uniform(0.5e9, 2e9, (s_, n_))
    eng = WirelessEngine(ncfg, fl)
    out = eng.montecarlo_rounds(gains_seq, ns, cpu, 4e6)
    for k in ("t_comp_bottleneck", "t_up_bottleneck", "n_evicted",
              "aou_hist"):
        assert k in out, k
    assert np.asarray(out["aou_hist"]).shape == \
        (4, 2, len(AOU_BUCKET_EDGES) + 1)
    np.testing.assert_allclose(
        np.asarray(out["t_comp_bottleneck"])
        + np.asarray(out["t_up_bottleneck"]),
        np.asarray(out["t_round"]), rtol=1e-5)


# ------------------------------------------------------- regression gate

def _regress(fresh_dir, baseline_dir):
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.regress", "--fresh",
         str(fresh_dir), "--baseline", str(baseline_dir)],
        cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})


def test_regress_gate_fails_on_3x_collapse(tmp_path):
    base = tmp_path / "base"
    fresh = tmp_path / "fresh"
    base.mkdir(), fresh.mkdir()
    rows = [{"n": 100, "k": 8, "drops": 64, "drops_per_s_jax": 900.0},
            {"n": 1000, "k": 8, "drops": 16, "drops_per_s_jax": 300.0}]
    doc = {"benchmark": "engine_throughput", "backend": "cpu",
           "smoke": False, "rows": rows}
    (base / "BENCH_engine_throughput.json").write_text(json.dumps(doc, allow_nan=False))
    bad = json.loads(json.dumps(doc, allow_nan=False))
    bad["rows"][1]["drops_per_s_jax"] /= 3.0  # 3x collapse on one row
    bad["rows"][1]["drops"] = 4  # sweep-size knob must not break matching
    (fresh / "BENCH_engine_throughput.json").write_text(json.dumps(bad, allow_nan=False))
    r = _regress(fresh, base)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout and "n=1000" in r.stdout


def test_regress_gate_passes_clean_and_reports_unmatched(tmp_path):
    base = tmp_path / "base"
    fresh = tmp_path / "fresh"
    base.mkdir(), fresh.mkdir()
    doc = {"rows": [{"n": 100, "drops_per_s": 500.0},
                    {"n": 9999, "drops_per_s": 100.0}]}
    (base / "BENCH_x.json").write_text(json.dumps(doc, allow_nan=False))
    ok = {"rows": [{"n": 100, "drops_per_s": 480.0},
                   {"n": 7, "drops_per_s": 1.0}]}  # n=7: no baseline row
    (fresh / "BENCH_x.json").write_text(json.dumps(ok, allow_nan=False))
    (fresh / "BENCH_new.json").write_text(json.dumps({"rows": []}, allow_nan=False))
    r = _regress(fresh, base)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "no baseline row" in r.stdout
    assert "BENCH_new.json: NEW" in r.stdout
