"""The q-D sweep's check on a small 3D machine: its reference placement is
the 2D reference's at q = 2, a sound run of every strategy passes, the
control and an altered placement fail, a program that places a job
elsewhere is refused in set-up, and the per-head reader reads the heads
the calls carry."""

import json
import time
import types

import jax
import numpy as np
import pytest

import control
import generator
import harness
from conftest import ROOT
from reference import placement_qd
from reference import traffic as reference_traffic

CELL = "table4_bg3d.omniwar"
STRATS = ["row", "diagonal", "full_spread", "rectangular", "l_shape",
          "random_endpoint", "random_switch"]


def make_root(path, config_changes=None, mix_changes=None) -> str:
    """A checkout-shaped directory at ``path`` whose ``table4_bg3d.omniwar``
    cell runs the cell's mix on a 4x4x4 HyperX of concentration 4 with
    every strategy: a 16-rank job, 40 warm-up cycles, every lane of the
    window checked; then the given changes."""
    bench, cell, config, mix = harness.load_cell(ROOT, CELL)
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config["topology"] = {"n": 4, "q": 3, "concentration": 4}
    mix.update(strategies=STRATS, ranks=16, warmup=40,
               check_lanes=len(STRATS))
    config.update(config_changes or {})
    mix.update(mix_changes or {})
    for sub in ("configs", "traffic"):
        (path / "b" / sub).mkdir(parents=True)
    (path / "b" / "configs" / "small.json").write_text(json.dumps(config))
    (path / "b" / "traffic" / "small.json").write_text(json.dumps(mix))
    bench["paths"] = ["b"]
    cfg["file"] = "b/configs/small.json"
    cell["traffic"] = "small"
    (path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(path)


@pytest.fixture
def small_root(tmp_path):
    return make_root(tmp_path)


@pytest.mark.parametrize("strategy", STRATS)
@pytest.mark.parametrize("n", [4, 8])
def test_placement_is_the_2d_reference_at_q2(strategy, n):
    for p in range(n):
        for size, seed in [(n * n, 0), (3 * n * n - 5, 7)]:
            np.testing.assert_array_equal(
                placement_qd.placement(strategy, n, 2, n, p, size, seed),
                reference_traffic.placement(strategy, n, n, p, size, seed))


def test_lane_is_the_2d_reference_lane_at_q2():
    bg = {"pattern": "random_permutation", "packets": 1, "seed": 99}
    for strategy in STRATS:
        a = placement_qd.interference_lane(strategy, 4, 2, 4, "all_to_all",
                                           16, 1, bg, 40)
        b = reference_traffic.interference_lane(strategy, 4, 4, "all_to_all",
                                                16, 1, bg, 40)
        for field in vars(a):
            np.testing.assert_array_equal(getattr(a, field),
                                          getattr(b, field))


def run_cell(root, capsys, seed=4_000_000_007):
    rc = harness.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "0.1", "--trace", "0"],
                      root=root, t_start=time.perf_counter(),
                      devices=jax.devices()[:1])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_3d_run_of_every_strategy_is_correct(small_root, capsys):
    out = run_cell(small_root, capsys)
    assert out["correct"] is True
    assert out["check"]["fields_mismatched"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"sim_lane_cycles_per_s", "setup_s"}
    assert out["attempted"] % len(STRATS) == 0


def test_control_fails(small_root):
    _, _, config, mix = harness.load_cell(small_root, CELL)
    work = control.control_cell(config, mix, 11)
    work.setup()
    numbers, _ = work.check([work.call(0)])
    value, limit = numbers["fields_mismatched"]
    assert value > limit


def test_one_placement_altered_fails(small_root):
    """The program's answers against a reference whose diagonal lane has
    two ranks' endpoints exchanged: the check finds the difference."""
    _, _, config, mix = harness.load_cell(small_root, CELL)
    mix.update(strategies=["diagonal"], check_lanes=1)
    work = generator.build(config, mix, 5)
    work.setup()
    calls = [work.call(0)]
    work.release()
    numbers, _ = work.check(calls)
    assert numbers["fields_mismatched"][0] == 0
    sound = work._lane

    def altered(self, strategy):
        lane = sound(strategy)
        lane.rank_ep[[0, 16]] = lane.rank_ep[[16, 0]]  # a job rank moves
        return lane

    work._lane = types.MethodType(altered, work)
    numbers, _ = work.check(calls)
    assert numbers["fields_mismatched"][0] >= 1


def test_program_placing_elsewhere_is_refused_in_setup(small_root,
                                                       monkeypatch):
    """A program whose placement is not the q-D form (such as one that
    maps every job into the plane s_0 = 0) fails before compiling."""
    import repro.traffic.scenario as scenario

    orig = scenario.allocate_partition

    def planar(strategy, topo, job_id, size=None, seed=0):
        part = orig(strategy, topo, job_id, size=size, seed=seed)
        return part.__class__(**{**vars(part),
                                 "endpoints": part.endpoints % 64})

    monkeypatch.setattr(scenario, "allocate_partition", planar)
    _, _, config, mix = harness.load_cell(small_root, CELL)
    work = generator.build(config, mix, 1)
    with pytest.raises(ValueError, match="other than the reference"):
        work.setup()
    assert work.engine is None


def test_reference_refuses_a_head_field_overflow(tmp_path):
    root = make_root(tmp_path, config_changes={
        "topology": {"n": 8, "q": 3, "concentration": 8},
        "engine": {"cap": 8, "penalty_packets": 4, "max_deroutes": 6}})
    _, _, config, mix = harness.load_cell(root, CELL)
    with pytest.raises(ValueError, match="17-bit head field"):
        generator.build(config, mix, 1)


class _Trace:
    busy = True

    def spans(self, name):
        return [(0, 10_000), (20_000, 30_000)] if name == "bench.call" else []

    def busy_ns(self, lo, hi):
        return 6_000


def test_per_head_reader():
    read = harness.load_reader("loop_ns_per_head_cycle.sweep_qd")
    kind = generator.load_kind("sweep_qd")
    recs = [kind.CallRecord(index=i, lanes=2, own_cycles=0, iterations=10,
                            failed=0, answers=[], heads=50)
            for i in range(3)]
    run = types.SimpleNamespace(trace=_Trace(), calls=recs)
    assert read(run) == pytest.approx(12_000 / (2 * 2 * 10 * 50))
    # a program that gives no heads per lane: the metric is left out
    plain = [generator.CallRecord(index=0, lanes=2, own_cycles=0,
                                  iterations=10, failed=0, answers=[])]
    assert read(types.SimpleNamespace(trace=_Trace(), calls=plain)) is None
    assert read(types.SimpleNamespace(trace=None, calls=recs)) is None
