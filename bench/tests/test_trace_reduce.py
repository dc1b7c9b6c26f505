"""The reduction from profiler trace to metrics, on synthetic intervals
and on a small trace recorded on a TPU v5e (``record_trace.py``)."""

import os

import numpy as np
import pytest

import generator
import harness
import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_table4_h2.xplane.pb.gz")


def test_union_and_gaps():
    iv = np.array([[0, 10], [5, 20], [30, 40], [35, 38]], dtype=float)
    assert trace_reduce.union_ns(iv, 0, 50) == 30
    assert trace_reduce.union_ns(iv, 8, 32) == 14
    assert trace_reduce.gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert trace_reduce.gaps(np.zeros((0, 2)), 1, 2) == [(1, 2)]


def test_short_op():
    name = "%fusion.729 = s32[860160]{0:T(1024)S(1)} fusion(s32[7,1,7680] %p)"
    assert trace_reduce.short_op(name) == "fusion.729 s32[860160]"
    assert trace_reduce.short_op("%copy-start = (s32[1]{0}, u32[]) x") \
        == "copy-start"


@pytest.fixture(scope="module")
def v5e():
    with open(DATA, "rb") as f:
        return trace_reduce.Trace.from_bytes(f.read())


def test_v5e_trace_reduces(v5e):
    assert list(v5e.busy) == ["/device:TPU:0"]
    (lo, hi), = v5e.spans("bench.window")
    (clo, chi), = v5e.spans("bench.call")
    assert lo <= clo < chi <= hi
    busy = v5e.busy_ns(lo, hi)
    assert 0 < busy < hi - lo
    ops = v5e.top_ops(lo, hi)
    assert 0 < len(ops) <= 10
    assert not any(name.startswith("while") for name, _ in ops)
    assert sum(s for _, s in ops) > 0
    gaps = v5e.idle_gaps(lo, hi)
    assert gaps and all(isinstance(n, str) and s > 0 for n, s in gaps)
    assert sum(s for _, s in gaps) <= (hi - lo - busy) / 1e9 + 1e-9


def test_v5e_trace_metrics(v5e):
    call = generator.CallRecord(index=0, lanes=7, own_cycles=14,
                                iterations=2, failed=0, answers=[])
    run = harness.Run(setup_s=1.0, window_start=0.0, window_end=1.0,
                      calls=[call], trace=v5e)
    idle = harness.load_reader("device_idle_share.sweep")(run)
    assert 0 < idle < 100
    loop = harness.load_reader("loop_us_per_lane_cycle.sweep")(run)
    assert loop > 0
    host = harness.load_reader("engine_host_ms.sweep")(run)
    assert host > 0
    assert harness.load_reader("lane_useful_share.sweep")(run) == 100.0
    run.trace = None
    assert harness.load_reader("device_idle_share.sweep")(run) is None
