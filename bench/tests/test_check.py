"""The cell's check on a small machine: a sound run and the mix's variants
pass it, the control and the faults a sweep can have fail it, and a
setting the reference does not model is refused."""

import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import pytest

import control
import generator
import harness
from conftest import CELL, ROOT, make_root
from repro.core.engine import SimEngine


def run_cell(root, capsys, seed=123456789012):
    rc = harness.main(["--workload", CELL, "--seed", str(seed),
                       "--seconds", "0.1", "--trace", "0"],
                      root=root, t_start=time.perf_counter(),
                      devices=jax.devices()[:1])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(small_root, capsys):
    out = run_cell(small_root, capsys)
    assert out["correct"] is True
    assert out["check"]["fields_mismatched"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"sim_lane_cycles_per_s", "setup_s"}
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("changes", [
    {"background": None},
    {"seeds_per_call": 2, "check_lanes": 14},
], ids=["isolated", "two_seeds_per_call"])
def test_mix_variant_is_correct(tmp_path, capsys, changes):
    root = make_root(tmp_path, mix_changes=changes)
    out = run_cell(root, capsys)
    assert out["correct"] is True
    lanes = len(harness.load_cell(root, CELL)[3]["strategies"])
    assert out["attempted"] % (lanes * changes.get("seeds_per_call", 1)) == 0


@pytest.mark.parametrize("config_changes, mix_changes", [
    ({"routing": "ugal"}, {}),
    ({"fabric_partitioning": "per_job"}, {}),
    ({"link_faults": 0.02}, {}),
    ({"engine": {"telemetry": True}}, {}),
    ({}, {"kernel": "all_reduce"}),
    ({}, {"link_faults": 0.02}),
    ({}, {"background": {"pattern": "tornado", "packets": 1, "seed": 1}}),
    ({}, {"kind": "no_such_kind"}),
], ids=["routing", "fabric", "config_faults", "engine_option", "kernel",
        "mix_faults", "background_pattern", "kind"])
def test_unmodelled_setting_is_refused(tmp_path, config_changes,
                                       mix_changes):
    root = make_root(tmp_path, config_changes, mix_changes)
    _, _, config, mix = harness.load_cell(root, CELL)
    with pytest.raises(ValueError):
        generator.build(config, mix, 1)


def test_control_fails(small_root):
    _, _, config, mix = harness.load_cell(small_root, CELL)
    for seed in (11, 12, 13):
        work = control.control_cell(config, mix, seed)
        work.setup()
        numbers, _ = work.check([work.call(0)])
        value, limit = numbers["fields_mismatched"]
        assert value > limit


def test_fault_answer_altered_fails(small_root, capsys, monkeypatch):
    orig = SimEngine._to_result

    def altered(self, out, prep):
        r = orig(self, out, prep)
        return dataclasses.replace(r, max_hops=r.max_hops + 1)

    monkeypatch.setattr(SimEngine, "_to_result", altered)
    out = run_cell(small_root, capsys)
    assert out["correct"] is False


def test_fault_half_batch_left_out_fails(small_root, capsys, monkeypatch):
    orig = SimEngine.run_grid

    def half(self, workloads, seeds=None, horizon=60_000):
        kept = list(workloads)[: (len(workloads) + 1) // 2]
        res = orig(self, kept, seeds=seeds, horizon=horizon)
        return [res[j % len(kept)] for j in range(len(workloads))]

    monkeypatch.setattr(SimEngine, "run_grid", half)
    out = run_cell(small_root, capsys)
    assert out["correct"] is False


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
