"""The program's spans and the device's name scopes in a trace
(``bench/spans.py``): the reduction on hand-made events, the reading of a
hand-made ``.xplane.pb``, the per-layer numbers on hand-made reductions,
and the recorded chip trace's readings by ``bench/trace_reduce.py``, which
the span reading leaves as they were."""
from __future__ import annotations

import pathlib
import sys

import pytest

from _bench_cells import REPO

sys.path.insert(0, str(REPO))

from bench import spans as S  # noqa: E402
from bench import trace_reduce as T  # noqa: E402

FIXTURE = pathlib.Path(__file__).parent / "data" / "mc_tiny.xplane.pb"
E, A = T.Event, S.Event


def test_recorded_chip_trace_readings_are_pinned():
    """The readings of the recorded chip trace, to the last digit; its host
    events carry no arguments and its ops no scope path."""
    red = T.reduce(T.load(str(FIXTURE)))
    assert red.window_s == 0.153181826 and red.busy_s == 0.000691519
    assert red.module_time(r"^jit__montecarlo_step$") == (
        pytest.approx(0.000530866, abs=1e-15), 4)
    assert red.module_time(r"^jit__step_core$") == (
        pytest.approx(7.7736e-05, abs=1e-15), 4)
    assert red.gaps[:3] == [(0.055602864, "bench.window"),
                            (0.055189229, "bench.window"),
                            (0.001518975, "PjitFunction(_step_core)")]
    assert len(red.op_s) == 352
    assert sum(red.op_s.values()) == pytest.approx(0.00071006, abs=1e-15)
    sp = S.reduce(T.load(str(FIXTURE)), *S.load(str(FIXTURE)))
    assert sp.span_n["bench.call"] == 2 and sp.span_args["bench.call"] == {}
    assert sp.span_s["bench.window"] == red.window_s
    assert sp.scope_s == {}


def test_covered_counts_the_busy_part_of_each_interval():
    busy = [(10, 40), (60, 80)]
    assert S.covered(busy, [(0, 100)]) == 50
    assert S.covered(busy, [(0, 15), (35, 65), (90, 95)]) == 15
    assert S.covered([], [(0, 10)]) == 0


def test_reduce_spans_and_scopes_hand_made():
    tr = T.Trace(modules=[[E("jit_a", 0, 100)]],
                 ops=[[E("fusion.1", 10, 30), E("fusion.2", 60, 20)]],
                 host=[E("bench.window", 0, 100),
                       E("server.train", 0, 100)])
    native = [A("bench.window", 0, 100),
              A("server.train", 0, 50, {"steps": 3, "r": 0, "tag": "x"}),
              A("server.train", 40, 30, {"steps": 2, "r": 1}),
              A("client.update", 5, 10, {"steps": 3}),
              A("late", 95, 20),
              A("before", -20, 10, {"steps": 9})]
    scoped = [[E("jit(f)/mc.admit/sort", 10, 30),
               E("jit(f)/while/body/mc.admit/add", 20, 10),
               E("jit(f)/mc.finish/mul", 60, 20)]]
    sp = S.reduce(tr, native, scoped)
    # two overlapping spans: union [0, 70), counted once each; the
    # harness's clock-shifted copy in ``tr.host`` is not read
    assert sp.span_s["server.train"] == pytest.approx(70e-9)
    assert sp.span_n["server.train"] == 2
    assert sp.span_args["server.train"] == {"steps": 5, "r": 1}
    # busy [10, 40) and [60, 70) of [0, 70): 30 ns idle inside it
    assert sp.span_idle_s["server.train"] == pytest.approx(30e-9)
    assert sp.span_idle_s["client.update"] == pytest.approx(5e-9)
    assert sp.span_s["late"] == pytest.approx(5e-9)   # clipped at the end
    assert "before" not in sp.span_n                  # started before it
    assert sp.scope_s["mc.admit"] == pytest.approx(30e-9)
    assert sp.scope_s["mc.finish"] == pytest.approx(20e-9)
    assert sp.scope_s["while"] == pytest.approx(10e-9)
    assert "sort" not in sp.scope_s and "add" not in sp.scope_s


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[] fusion()"
    stats { metadata_id: 2 str_value: "jit(f)/mc.admit/gt" } } }
  event_metadata { key: 4 value { id: 4 name: "%sort.2 = f32[] sort()"
    stats { metadata_id: 2 ref_value: 6 } } }
  event_metadata { key: 5 value { id: 5 name: "jit_f(123)" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 6 value { id: 6 name: "jit(f)/mc.finish/sort" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000
      stats { metadata_id: 1 int64_value: 3 } } }
  event_metadata { key: 1 value { id: 1 name: "server.train" } }
  stat_metadata { key: 1 value { id: 1 name: "steps" } }
}
"""


def test_load_reads_scope_paths_and_span_arguments(tmp_path):
    """An op's scope path kept on its event metadata (as a TPU keeps
    ``tf_op``, by value or by reference) and a host span's arguments."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    assert S.scope_paths(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[] fusion()": "jit(f)/mc.admit/gt",
        "%sort.2 = f32[] sort()": "jit(f)/mc.finish/sort"}}
    native, scoped = S.load(str(path))
    assert [e.name for e in scoped[0]] == ["jit(f)/mc.admit/gt",
                                           "jit(f)/mc.finish/sort"]
    assert [(e.name, e.args) for e in native] == [("server.train",
                                                   {"steps": 3})]
    tr = T.load(str(path))
    tr.host.append(E("bench.window", 500, 9000))
    sp = S.reduce(tr, native, scoped)
    assert sp.scope_s == {"jit(f)": pytest.approx(2e-6),
                          "mc.admit": pytest.approx(1e-6),
                          "mc.finish": pytest.approx(1e-6)}
    assert sp.span_args["server.train"] == {"steps": 3}
    assert sp.span_idle_s["server.train"] == pytest.approx(7e-6)


def _spans(**fields) -> S.Spans:
    return S.Spans(**{**dict(span_s={}, span_n={}, span_args={},
                             span_idle_s={}, scope_s={}), **fields})


RED = T.Reduction(window_s=1.0, busy_s=0.5,
                  module_s={"jit__montecarlo_step": 0.02},
                  module_n={"jit__montecarlo_step": 4}, op_s={}, gaps=[])

CASES = [
    ("sgd_step_idle_ms", dict(span_args={"server.train": {"steps": 10}},
                              span_idle_s={"server.train": 0.02}), 2.0),
    ("fl_aggregate_ms", dict(span_s={"server.aggregate": 0.3},
                             span_n={"server.aggregate": 2}), 150.0),
    ("fl_select_ms", dict(span_s={"server.scenario": 0.01,
                                  "server.select": 0.03},
                          span_n={"server.scenario": 2,
                                  "server.select": 2}), 20.0),
    ("mc_entry_host_ms", dict(span_s={"mc.call": 0.8,
                                      "engine.mc_loop": 0.6},
                              span_n={"mc.call": 4}), 50.0),
    ("mc_admit_device_ms", dict(scope_s={"mc.admit": 0.012}), 3.0),
]


@pytest.mark.parametrize("name,fields,want", CASES,
                         ids=[c[0] for c in CASES])
def test_number_on_a_hand_made_reduction(name, fields, want):
    """Each number where its span or scope is there, and nothing where it
    is not (as in a trace of a program that has no such span)."""
    fn = getattr(S, name)
    assert fn(_spans(**fields), RED) == pytest.approx(want)
    assert fn(_spans(), RED) is None
    out = S.numbers(_spans(**fields), RED)
    assert {k: v for k, v in out.items() if not k.startswith("covered.")} \
        == {name: pytest.approx(want)}


def test_numbers_give_the_share_children_cover():
    sp = _spans(span_s={"mc.call": 0.2, "mc.setup": 0.01,
                        "engine.mc_loop": 0.15, "mc.collect": 0.03},
                span_n={"mc.call": 2})
    out = S.numbers(sp, RED)
    assert out["covered.mc.call"] == pytest.approx(0.95)
    assert out["mc_entry_host_ms"] == pytest.approx(25.0)
    assert "covered.server.round" not in out
