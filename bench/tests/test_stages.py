"""Device time by cycle stage (``stages.py``) and the engine's host-stage
readers, on the committed v5e trace and on a synthetic one."""

import os

import numpy as np
import pytest

import generator
import harness
import stages
import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_table4_h2.xplane.pb.gz")
ENGINE_READERS = ("engine_lower_ms.sweep", "engine_results_ms.sweep")


@pytest.fixture(scope="module")
def v5e():
    with open(DATA, "rb") as f:
        data = f.read()
    return trace_reduce.Trace.from_bytes(data), stages.op_scopes(data)


def _run(trace, calls):
    return harness.Run(setup_s=1.0, window_start=0.0, window_end=1.0,
                       calls=calls, trace=trace)


def _call(lanes, iterations):
    return generator.CallRecord(index=0, lanes=lanes, own_cycles=1,
                                iterations=iterations, failed=0, answers=[])


def test_op_scopes_on_v5e_trace(v5e):
    """The committed trace predates the stage scopes: its loop body ops
    carry bare op labels, and all of them count as ``loop``."""
    trace, scopes = v5e
    name, = [n for n in trace.op_names if n.startswith("%fusion.722 ")]
    assert scopes[name] == "jit(core)/vmap(vmap())/while/body/gather:"
    (lo, hi), = trace.spans("bench.call")
    by_stage = stages.stage_ns(trace, scopes, lo, hi)
    assert set(by_stage) == {"loop"}
    assert by_stage["loop"] == pytest.approx(trace.busy_ns(lo, hi), rel=0.01)
    out = stages.breakdown(trace, scopes, lane_iterations=14)["calls"][0]
    loop = harness.load_reader("loop_us_per_lane_cycle.sweep")(
        _run(trace, [_call(7, 2)]))
    assert out["busy_us_per_lane_iteration"] == pytest.approx(loop)
    # nor has it the engine's spans: the readers leave their metrics out
    for name in ENGINE_READERS:
        assert harness.load_reader(name)(_run(trace, [_call(7, 2)])) is None


def test_stage_of():
    body = "jit(core)/vmap(vmap())/while/body/"
    assert stages.stage_of(body + "route/jit(take_along_axis)/gather:") \
        == "route"
    assert stages.stage_of(body + "heads:") == "heads"
    assert stages.stage_of(body + "heads_x/add:") == "loop"
    assert stages.stage_of(body + "body_pred/and:") == "loop"
    assert stages.stage_of("jit(core)/vmap(vmap())/while:") == "loop"
    assert stages.stage_of("jit(core)/while/body/while/body/inject/add:") \
        == "inject"
    assert stages.stage_of("jit(dynamic_slice)/dynamic_slice:") is None
    assert stages.stage_of("") is None


def test_stages_and_engine_spans_on_synthetic_trace():
    """Stage sums and engine span readers count only what lies inside
    the ``bench.call`` spans, per lane-iteration and per call."""
    body = "jit(core)/vmap(vmap())/while/body/"
    scoped = [body + "heads/gather:", body + "route/lt:",
              body + "arbitrate/scatter-min:", "jit(core)/vmap(vmap())/while:",
              "jit(dynamic_slice)/dynamic_slice:"]
    names = [f"%op.{i} = s32[8]{{0}} fusion()" for i in range(6)]
    scopes = dict(zip(names, scoped))  # names[5] has no scope
    ops = np.array([
        [0, 1000, 1100], [1, 1100, 1500], [2, 1500, 2100], [3, 2100, 2300],
        [4, 2300, 2310], [5, 2310, 2320],
        [0, 6000, 6100], [2, 6100, 6700],
        [0, 9500, 9900],                      # outside every call
    ], dtype=float)
    host = [("bench.call", 1000.0, 5000.0), ("bench.call", 6000.0, 9000.0),
            ("engine.prepare", 1000.0, 1200.0),
            ("engine.stack", 1200.0, 1300.0),
            ("engine.dispatch", 1300.0, 4000.0),
            ("engine.to_result", 4000.0, 4990.0),
            ("engine.to_result", 8000.0, 8600.0),
            ("engine.to_result", 9500.0, 9900.0)]
    trace = trace_reduce.Trace(
        busy={"/device:TPU:0": np.array([[1000, 2320], [6000, 6700]],
                                        dtype=float)},
        op_names=names, ops=ops, host=host)
    assert stages.stage_ns(trace, scopes, 1000, 5000) == {
        "heads": 100, "route": 400, "arbitrate": 600, "loop": 200}
    calls = stages.breakdown(trace, scopes, lane_iterations=10)["calls"]
    assert calls[0]["stage_us_per_lane_iteration"] == pytest.approx(
        {"arbitrate": 0.06, "route": 0.04, "loop": 0.02, "heads": 0.01})
    assert calls[0]["engine_ms"]["engine.dispatch"] == pytest.approx(2.7e-3)
    assert calls[0]["uncovered_ms"] == pytest.approx(1e-5)
    assert calls[1]["uncovered_ms"] == pytest.approx(2.4e-3)
    run = _run(trace, [_call(2, 5), _call(2, 5)])
    read = {n: harness.load_reader(n)(run) for n in ENGINE_READERS}
    assert read["engine_lower_ms.sweep"] == pytest.approx(300 / 2 / 1e6)
    assert read["engine_results_ms.sweep"] == pytest.approx(1590 / 2 / 1e6)
