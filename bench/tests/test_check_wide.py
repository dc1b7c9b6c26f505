"""The wide-key sweep's check: at 22x22 the program places every strategy
where the reference does; on a small machine whose head field is widened
(the 17-bit minimum raised in the engine and in the reference) a sound run
passes and the control fails; unmodelled settings and machines past the
random-bit floor are refused; the per-head-and-port reader reads the
sizes the calls carry.  The four-chip mix loads with four seeds a call."""

import json
import time
import types

import jax
import pytest

import control
import generator
import harness
from conftest import ROOT
from reference import wide_key
from repro.core.engine import runner, tables

CELL = "table4_bg22.omniwar"
STRATS = ["row", "diagonal", "full_spread", "rectangular", "l_shape",
          "random_endpoint", "random_switch"]


def make_root(path, config_changes=None, mix_changes=None) -> str:
    """A checkout-shaped directory at ``path`` whose ``table4_bg22.omniwar``
    cell runs the cell's mix on a 4x4 HyperX of concentration 4 with every
    strategy: a 16-rank job, 40 warm-up cycles, every lane checked; then
    the given changes."""
    bench, cell, config, mix = harness.load_cell(ROOT, CELL)
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config["topology"] = {"n": 4, "q": 2, "concentration": 4}
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


@pytest.fixture
def wide_field(monkeypatch):
    """A 19-bit head field at n = 4 (960 heads), in the program and in the
    reference, with no engine or tables of another width cached."""
    monkeypatch.setattr(tables, "MIN_HEAD_BITS", 19)
    monkeypatch.setattr(wide_key, "MIN_HEAD_BITS", 19)
    tables.build_static_tables.cache_clear()
    runner._engine_for.cache_clear()
    yield 19
    tables.build_static_tables.cache_clear()
    runner._engine_for.cache_clear()


@pytest.fixture(scope="module")
def radix64_cell():
    """The cell's kind on the full 22x22 machine, every strategy, before
    set-up (nothing compiled)."""
    from repro.core.hyperx import HyperX

    _, _, config, mix = harness.load_cell(ROOT, CELL)
    work = generator.build(config, dict(mix, strategies=STRATS), 1)
    topo = config["topology"]
    work._topo = HyperX(n=topo["n"], q=topo["q"],
                        concentration=topo["concentration"])
    return work


def test_radix64_reference_machine(radix64_cell):
    mc = radix64_cell.mc
    assert (mc.S, mc.E, mc.QN, mc.V, mc.NQ) == (484, 10_648, 44, 5, 159_720)
    assert radix64_cell.head_bits == 18


@pytest.mark.parametrize("strategy", STRATS)
def test_radix64_placement_is_the_reference(radix64_cell, strategy):
    """The set-up check: the program's endpoints of the 484-rank job and
    its 10,164-endpoint background equal the reference lowering's."""
    w = radix64_cell._workload(strategy)
    assert len(w.rank_ep) == 10_648


@pytest.mark.parametrize("config_changes, mix_changes", [
    ({"routing": "ugal"}, {}),
    ({"link_faults": 0.02}, {}),
    ({"engine": {"telemetry": True}}, {}),
    ({}, {"kernel": "all_reduce"}),
    ({}, {"link_faults": 0.02}),
], ids=["routing", "config_faults", "engine_option", "kernel", "mix_faults"])
def test_unmodelled_setting_is_refused(tmp_path, config_changes,
                                       mix_changes):
    root = make_root(tmp_path, config_changes, mix_changes)
    _, _, config, mix = harness.load_cell(root, CELL)
    with pytest.raises(ValueError):
        generator.build(config, mix, 1)


def test_machine_past_the_random_floor_is_refused(tmp_path):
    """n = 22 with 600 deroutes: 603 VCs, 19.3 million heads a lane, 7
    random bits."""
    root = make_root(tmp_path, config_changes={
        "topology": {"n": 22, "q": 2, "concentration": 22},
        "engine": {"cap": 8, "penalty_packets": 4, "max_deroutes": 600}})
    _, _, config, mix = harness.load_cell(root, CELL)
    with pytest.raises(ValueError, match="fewer than 8"):
        generator.build(config, mix, 1)


def test_sound_wide_run_is_correct(small_root, wide_field, capsys,
                                   monkeypatch):
    calls = []
    kind = generator.load_kind("sweep_wide")
    orig = kind.Cell.call

    def spy(self, i):
        calls.append(orig(self, i))
        return calls[-1]

    monkeypatch.setattr(generator, "load_kind", lambda name: kind)
    monkeypatch.setattr(kind.Cell, "call", spy)
    rc = harness.main(["--workload", CELL, "--seed", "3000000019",
                       "--seconds", "0.1", "--trace", "0"],
                      root=small_root, t_start=time.perf_counter(),
                      devices=jax.devices()[:1])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["check"]["fields_mismatched"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"sim_lane_cycles_per_s", "setup_s"}
    assert {(c.heads, c.head_bits, c.net_ports) for c in calls} == \
        {(16 * 12 * 5, wide_field, 8)}


def test_control_fails(small_root, wide_field):
    _, _, config, mix = harness.load_cell(small_root, CELL)
    work = control.control_cell(config, mix, 11)
    assert work.head_bits == wide_field
    work.setup()
    numbers, _ = work.check([work.call(0)])
    value, limit = numbers["fields_mismatched"]
    assert value >= 1 and value > limit


def test_four_chip_mix_takes_four_seeds_a_call():
    _, cell, config, mix = harness.load_cell(ROOT, "table4_bg.omniwar.4chip")
    assert cell["chips"] == 4 and mix["kind"] == "sweep"
    work = generator.build(config, mix, 1)
    assert work.per_call == 4
    assert [work.call_seeds(i) for i in range(3)] == \
        [[1, 2, 3, 1], [2, 3, 1, 2], [3, 1, 2, 3]]
    assert len(work.strategies) * work.per_call == 28


class _Trace:
    busy = True

    def spans(self, name):
        return [(0, 10_000), (20_000, 30_000)] if name == "bench.call" else []

    def busy_ns(self, lo, hi):
        return 6_000


def test_per_head_port_reader():
    read = harness.load_reader("loop_ns_per_head_port_cycle.sweep_wide")
    kind = generator.load_kind("sweep_wide")
    recs = [kind.CallRecord(index=i, lanes=1, own_cycles=0, iterations=10,
                            failed=0, answers=[], heads=50, head_bits=18,
                            net_ports=4)
            for i in range(3)]
    run = types.SimpleNamespace(trace=_Trace(), calls=recs)
    assert read(run) == pytest.approx(12_000 / (2 * 1 * 10 * 50 * 4))
    # a program that gives no heads per lane: the metric is left out
    no_heads = [kind.CallRecord(index=0, lanes=1, own_cycles=0,
                                iterations=10, failed=0, answers=[],
                                net_ports=4)]
    assert read(types.SimpleNamespace(trace=_Trace(), calls=no_heads)) is None
    assert read(types.SimpleNamespace(trace=None, calls=recs)) is None
