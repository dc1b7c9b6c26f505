"""Observability tests.

The load-bearing pins:

  * **telemetry neutrality** — a disabled ``TelemetrySpec`` (the default)
    produces bitwise-identical ``SimResult`` values AND identical compile
    counts to the pre-telemetry engine, across ``run_batch_seeds`` and
    ``run_grid``, on every registered routing policy;
  * enabled telemetry leaves the physics untouched (results still equal
    the reference bitwise) and its accumulators satisfy conservation
    invariants (injected = delivered = latency-histogram mass);
  * ``TelemetrySpec`` is part of the ``get_engine`` memo key;
  * the tracer writes parseable JSONL + manifest and the report renders;
  * tracing off is zero-cost: one shared nullcontext, no allocation;
  * every op of the cycle loop's body lies under one stage scope, and the
    engine's host stages land on the profiler's clock.
"""

import glob
import json
import os
import re

import numpy as np
import pytest

from repro.core import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine, get_engine
from repro.core.hyperx import HyperX
from repro.obs import TelemetrySpec
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.obs.probes import Telemetry
from repro.route import available_policies

SMALL = HyperX(n=4, q=2)


def _a2a(strategy: str):
    part = allocate_partition(strategy, SMALL, 0)
    return tr.compose_workload(SMALL, [(tr.all_to_all(16), part)])


# ------------------------------------------------------------- neutrality
@pytest.mark.parametrize("mode", available_policies())
def test_telemetry_off_bitwise_and_compile_neutral(mode):
    """The acceptance pin: default-off telemetry is invisible — same
    results bit-for-bit, same trace counts — on every routing policy."""
    base = SimEngine(SMALL, mode=mode)
    off = SimEngine(SMALL, mode=mode, telemetry=None)
    wls = [_a2a(s) for s in ("row", "diagonal")]
    seeds = (0, 3)

    ref_bs = base.run_batch_seeds(wls, seeds=seeds, horizon=4000)
    assert off.run_batch_seeds(wls, seeds=seeds, horizon=4000) == ref_bs
    ref_grid = base.run_grid(wls, seeds=seeds, horizon=4000)
    assert off.run_grid(wls, seeds=seeds, horizon=4000) == ref_grid
    assert off.trace_count == base.trace_count
    assert off.device_calls == base.device_calls
    for per_seed in ref_bs + ref_grid:
        for r in per_seed:
            assert r.telemetry is None


@pytest.mark.parametrize("mode", ["omniwar", "min"])
def test_telemetry_on_does_not_change_results(mode):
    """Enabled probes observe the simulation without perturbing it:
    SimResult equality (telemetry is compare=False) must still hold."""
    base = SimEngine(SMALL, mode=mode)
    on = SimEngine(SMALL, mode=mode, telemetry=TelemetrySpec())
    wls = [_a2a(s) for s in ("row", "diagonal")]
    seeds = (0, 3)
    ref = base.run_grid(wls, seeds=seeds, horizon=4000)
    got = on.run_grid(wls, seeds=seeds, horizon=4000)
    assert got == ref
    assert on.trace_count == base.trace_count  # one per bucket, still
    for per_seed in got:
        for r in per_seed:
            assert isinstance(r.telemetry, Telemetry)


def test_telemetry_invariants_and_grid_parity():
    """Conservation: every delivered packet lands in exactly one window
    and one latency bin; occupancy histograms sample every queue every
    cycle; run() and run_grid() accumulate identical series."""
    spec = TelemetrySpec()
    engine = SimEngine(SMALL, mode="omniwar", telemetry=spec)
    wl = _a2a("row")
    res = engine.run(wl, seed=0, horizon=4000)
    tel = res.telemetry
    assert tel is not None and tel.spec == spec

    packets = 16 * 15  # 16-rank all-to-all
    assert int(tel.injected.sum()) == packets
    assert int(tel.delivered.sum()) == packets
    assert int(tel.lat_hist.sum()) == packets
    assert int(tel.cycles.sum()) == tel.total_cycles > 0
    # occupancy histograms: one sample per (pool-queue, cycle)
    occ = tel.vc_occ  # (W, P*(CAP+1))
    num_queues = int(occ.sum()) // max(tel.total_cycles, 1)
    assert occ.sum() == num_queues * tel.total_cycles
    util = tel.link_utilization()
    assert util.shape == (tel.S, tel.net_ports)
    # the 2x crossbar speedup bounds a link at 2 grants/cycle
    assert float(util.max()) <= 2.0 + 1e-6
    assert len(tel.hottest_links(5)) == 5
    assert np.nanmax(tel.mean_latency()) > 0
    # the summary digest is JSON-serializable as emitted
    json.dumps(tel.summary("row"), default=obs_trace._json_default)

    # grid lanes accumulate the same series as the single run
    grid = engine.run_grid([wl], seeds=(0,), horizon=4000)
    gtel = grid[0][0].telemetry
    assert np.array_equal(gtel.link_util, tel.link_util)
    assert np.array_equal(gtel.lat_hist, tel.lat_hist)
    assert np.array_equal(gtel.vc_occ, tel.vc_occ)


def test_get_engine_telemetry_in_key():
    e0 = get_engine(SMALL, mode="omniwar")
    e1 = get_engine(SMALL, mode="omniwar", telemetry=TelemetrySpec())
    e2 = get_engine(SMALL, mode="omniwar", telemetry=TelemetrySpec())
    assert e0 is not e1
    assert e1 is e2  # spec is a frozen dataclass: equal specs share
    assert get_engine(SMALL, mode="omniwar") is e0
    assert e1.telemetry == TelemetrySpec()


def test_telemetry_spec_validation():
    with pytest.raises(ValueError):
        TelemetrySpec(n_windows=0)
    with pytest.raises(ValueError):
        TelemetrySpec(window=0)
    with pytest.raises(ValueError):
        TelemetrySpec(lat_bins=0)


# ----------------------------------------------------------------- tracing
def test_tracer_jsonl_manifest_and_report(tmp_path):
    d = str(tmp_path / "trace")
    try:
        obs_trace.configure(d, run_id="t1", suite="unit")
        with obs_trace.span("unit.work", grid="g"):
            obs_trace.event("unit.mark", job=7)
        obs_trace.counter("unit.count", 3)
        obs_trace.gauge("sched.frag", 0.25, stream="s/p", t_sim=1.0)
        obs_trace.event("sched.start", stream="s/p", job=1, backfilled=True)
        obs_trace.event("sched.arrive", stream="s/p", job=1)
    finally:
        obs_trace.disable()

    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["run_id"] == "t1"
    assert manifest["suite"] == "unit"
    assert manifest["schema"] == obs_trace.SCHEMA
    assert manifest["config_hash"]
    assert manifest["lane_backend"] in ("vmap", "shard_map")

    with open(os.path.join(d, "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    names = [e["name"] for e in events]
    assert names[0] == "trace.start" and names[-1] == "trace.end"
    spans = [e for e in events if e["type"] == "span"]
    assert spans and spans[0]["name"] == "unit.work"
    assert spans[0]["dur_s"] >= 0 and spans[0]["grid"] == "g"

    paths = obs_report.write_report(d)
    assert os.path.exists(paths["report"])
    assert os.path.exists(paths["spans"])
    sched = obs_report.sched_rows(events)
    assert sched == [{
        "stream": "s/p", "arrived": 1, "started": 1, "backfilled": 1,
        "finished": 0, "migrations": 0, "requeues": 0, "failures": 0,
        "frag_mean": 0.25, "frag_max": 0.25, "utilization": "",
    }]


def test_engine_dispatch_spans(tmp_path):
    d = str(tmp_path / "trace")
    engine = SimEngine(SMALL, mode="omniwar")
    wl = _a2a("row")
    try:
        obs_trace.configure(d)
        engine.run(wl, seed=0, horizon=4000)
    finally:
        obs_trace.disable()
    with open(os.path.join(d, "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    spans = [e for e in events if e.get("name") == "engine.dispatch"]
    assert spans and spans[0]["api"] == "run"
    compiles = [e for e in events if e.get("name") == "engine.compile"]
    assert len(compiles) == engine.trace_count == 1


STAGES = ("heads", "route", "arbitrate", "dequeue", "deliver", "move",
          "complete", "inject")


@pytest.mark.parametrize("kernel", ["lax", "pallas"])
def test_cycle_stages_name_every_loop_body_op(kernel):
    """The core's lowered text tags each op of the loop body with a stage
    scope: all of the default path's, ``route_arbitrate`` in place of
    ``route`` and ``arbitrate`` for the fused kernel, and nothing else."""
    engine = SimEngine(SMALL, mode="omniwar", kernel=kernel)
    prep = engine.prepare(_a2a("row"))
    text = engine._run1.lower(prep.tables, np.int32(0), np.int32(8)).as_text(
        debug_info=True)
    body = [n for n in re.findall(r'loc\("([^"]*)"', text)
            if n.startswith("jit(core)/while/body/")]
    assert body
    seen = {n.split("/")[3] for n in body}
    want = set(STAGES)
    if kernel == "pallas":
        want = want - {"route", "arbitrate"} | {"route_arbitrate"}
    assert seen == want


def test_engine_stages_on_profiler_clock(tmp_path):
    """A ``run_grid`` call under the profiler shows the engine's four host
    stages inside the call, in order; ``new_key`` marks the compile."""
    from jax import profiler

    engine = SimEngine(SMALL, mode="omniwar")
    wls = [_a2a("row"), _a2a("diagonal")]
    obs_trace.disable()
    options = profiler.ProfileOptions()
    options.python_tracer_level = 0  # the spans alone; keeps the test short
    profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(2):
            with profiler.TraceAnnotation("test.call", index=i):
                engine.run_grid(wls, seeds=[0, 1], horizon=2)
    finally:
        profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    order = ("engine.prepare", "engine.stack", "engine.dispatch",
             "engine.to_result")
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats) if e.name in order else {})
              for plane in profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    calls = sorted((s, e) for n, s, e, _ in events if n == "test.call")
    assert len(calls) == 2
    for i, (lo, hi) in enumerate(calls):
        inside = sorted((s, e, n, st) for n, s, e, st in events
                        if n in order and lo <= s and e <= hi)
        assert [n for _, _, n, _ in inside] == list(order)
        assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))
        dispatch = inside[2][3]
        assert dispatch["new_key"] == (1 if i == 0 else 0)
        assert dispatch["api"] == "run_grid"
        assert dispatch["lanes"] == dispatch["padded_lanes"] == 4
        assert inside[3][3]["arrays"] == 4 * 11


def test_span_off_is_shared_nullcontext():
    obs_trace.disable()
    assert obs_trace.active() is None
    s1 = obs_trace.span("a")
    s2 = obs_trace.span("b", attr=1)
    assert s1 is s2  # the shared singleton: no per-call allocation
    with s1:
        pass
    # emitters are silent no-ops with no tracer
    obs_trace.event("noop")
    obs_trace.counter("noop", 1)
    obs_trace.gauge("noop", 1.0)
    obs_trace.log_telemetry("noop", None)


def test_scheduler_emits_stream_events(tmp_path):
    from repro.sched.jobs import poisson_stream
    from repro.sched.scheduler import OnlineScheduler

    d = str(tmp_path / "trace")
    jobs = poisson_stream(8, seed=3)
    try:
        obs_trace.configure(d)
        res = OnlineScheduler(SMALL, strategy="diagonal",
                              analyze=False).run_stream(jobs)
    finally:
        obs_trace.disable()
    with open(os.path.join(d, "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    rows = obs_report.sched_rows(events)
    assert len(rows) == 1
    row = rows[0]
    assert row["stream"] == "diagonal/first_fit"
    assert row["arrived"] == len(jobs)
    assert row["finished"] == len(jobs)
    assert row["utilization"] == round(res.utilization, 4)
    assert row["frag_max"] == round(res.frag_max, 4)
