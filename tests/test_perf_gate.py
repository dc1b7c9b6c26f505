"""Perf-gate tests: BENCH json comparison logic and the nonzero exit on a
synthetic >10% device-time regression (no measurement is run — run_suite
is stubbed; the measuring path is covered by the CI perf-smoke job)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # benchmarks/ is a namespace package at repo root
    sys.path.insert(0, REPO)

from benchmarks import perf  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_history(tmp_path, monkeypatch):
    """Every main() run appends to the history trajectory — point it at a
    scratch file so tests never pollute the committed BENCH_history.jsonl."""
    monkeypatch.setattr(perf, "HISTORY_PATH",
                        str(tmp_path / "BENCH_history.jsonl"))


def _bench(device_s_by_grid, rev="test"):
    return {
        "schema": perf.SCHEMA, "rev": rev, "quick": True, "backend": "cpu",
        "devices": 1, "jax": "x", "arb": "lax", "kernel": "lax",
        "chunk": 1,
        "grids": {
            g: {"lanes": 4, "buckets": 1, "traces": 1, "lane_backend": "vmap",
                "compile_s": 1.0, "device_s": d, "cycles": 1000,
                "cycles_per_s": 1000 / d, "lanes_per_s": 4 / d}
            for g, d in device_s_by_grid.items()
        },
    }


def test_compare_flags_only_past_threshold():
    base = _bench({"a": 1.0, "b": 2.0, "c": 3.0})
    new = _bench({"a": 1.05, "b": 2.3, "c": 2.0})  # +5%, +15%, -33%
    rows = perf.compare_benchmarks(new, base, threshold=0.10)
    flagged = {r["grid"]: r["regressed"] for r in rows}
    assert flagged == {"a": False, "b": True, "c": False}


def test_compare_tolerates_missing_grids():
    rows = perf.compare_benchmarks(
        _bench({"a": 1.0}), _bench({"b": 1.0}), threshold=0.10)
    assert all(not r["regressed"] for r in rows)
    assert {r["grid"] for r in rows} == {"a", "b"}


def test_main_exits_nonzero_on_synthetic_regression(tmp_path, monkeypatch):
    """The acceptance pin: a synthetic 10%+ slowdown vs the baseline makes
    `perf.py --compare` return nonzero; an equal run returns zero."""
    base_path = tmp_path / "BENCH_base.json"
    base_path.write_text(json.dumps(_bench({"g": 1.0}, rev="base")))

    def fake_suite(slow):
        def run_suite(quick=True, grids=None, arb="lax", **kw):
            return _bench({"g": 1.1 * 1.001 if slow else 1.0}, rev="new")
        return run_suite

    out = tmp_path / "BENCH_new.json"
    monkeypatch.setattr(perf, "run_suite", fake_suite(slow=True))
    rc = perf.main(["--quick", "--out", str(out), "--compare",
                    str(base_path)])
    assert rc != 0
    assert json.loads(out.read_text())["rev"] == "new"  # snapshot still lands

    monkeypatch.setattr(perf, "run_suite", fake_suite(slow=False))
    rc = perf.main(["--quick", "--out", str(out), "--compare",
                    str(base_path)])
    assert rc == 0


def test_compare_missing_baseline_fails_fast(tmp_path, monkeypatch, capsys):
    """A missing baseline exits with the distinct bad-baseline code and a
    one-line error BEFORE any measurement runs."""
    def boom(*a, **k):
        raise AssertionError("run_suite must not run with a bad baseline")
    monkeypatch.setattr(perf, "run_suite", boom)
    rc = perf.main(["--quick", "--out", str(tmp_path / "o.json"),
                    "--compare", str(tmp_path / "nope.json")])
    assert rc == perf.EXIT_BAD_BASELINE
    assert rc != perf.EXIT_REGRESSION
    err = capsys.readouterr().err
    assert "cannot read baseline" in err and len(err.strip().splitlines()) == 1


@pytest.mark.parametrize("payload", ["{not json", '{"schema": 1}', '[1,2]'])
def test_compare_corrupt_baseline_fails_fast(tmp_path, monkeypatch, capsys,
                                             payload):
    base = tmp_path / "BENCH_bad.json"
    base.write_text(payload)
    monkeypatch.setattr(
        perf, "run_suite",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("measured")))
    rc = perf.main(["--quick", "--out", str(tmp_path / "o.json"),
                    "--compare", str(base)])
    assert rc == perf.EXIT_BAD_BASELINE
    assert "baseline" in capsys.readouterr().err


def test_main_writes_bench_json_and_baseline(tmp_path, monkeypatch):
    monkeypatch.setattr(perf, "run_suite",
                        lambda quick=True, grids=None, arb="lax", **kw:
                        _bench({"g": 1.0}, rev="abc123"))
    out = tmp_path / "BENCH_abc123.json"
    rc = perf.main(["--quick", "--out", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert payload["grids"]["g"]["device_s"] == 1.0
    assert payload["schema"] == perf.SCHEMA


# ------------------------------------------------------------------- history
def test_every_run_appends_history(tmp_path, monkeypatch):
    """The trajectory contract: each main() run adds exactly one jsonl
    entry carrying rev, date, and the per-grid metric table."""
    monkeypatch.setattr(perf, "run_suite",
                        lambda *a, **k: _bench({"g": 1.0}, rev="r1"))
    assert perf.main(["--quick", "--out", str(tmp_path / "a.json")]) == 0
    monkeypatch.setattr(perf, "run_suite",
                        lambda *a, **k: _bench({"g": 1.0}, rev="r2"))
    assert perf.main(["--quick", "--out", str(tmp_path / "b.json")]) == 0
    lines = [json.loads(ln) for ln in
             open(perf.HISTORY_PATH).read().splitlines() if ln]
    assert [e["rev"] for e in lines] == ["r1", "r2"]
    assert all("date" in e and "grids" in e for e in lines)
    assert perf.latest_history()["rev"] == "r2"


def test_bare_compare_gates_against_latest_history(tmp_path, monkeypatch):
    """`--compare` with no path reads the latest prior history entry: a
    matching run passes, a >10% device_s slowdown fails the gate."""
    monkeypatch.setattr(perf, "run_suite",
                        lambda *a, **k: _bench({"g": 1.0}, rev="base"))
    assert perf.main(["--quick", "--out", str(tmp_path / "a.json")]) == 0

    monkeypatch.setattr(perf, "run_suite",
                        lambda *a, **k: _bench({"g": 1.0}, rev="same"))
    assert perf.main(["--quick", "--out", str(tmp_path / "b.json"),
                      "--compare"]) == 0

    monkeypatch.setattr(perf, "run_suite",
                        lambda *a, **k: _bench({"g": 1.2}, rev="slow"))
    rc = perf.main(["--quick", "--out", str(tmp_path / "c.json"),
                    "--compare"])
    assert rc == perf.EXIT_REGRESSION


def test_bare_compare_without_history_fails_fast(tmp_path, monkeypatch,
                                                 capsys):
    monkeypatch.setattr(
        perf, "run_suite",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("measured")))
    rc = perf.main(["--quick", "--out", str(tmp_path / "o.json"),
                    "--compare"])
    assert rc == perf.EXIT_BAD_BASELINE
    assert "no prior" in capsys.readouterr().err


def test_latest_history_skips_corrupt_lines(tmp_path, monkeypatch):
    hist = tmp_path / "BENCH_history.jsonl"
    good = json.dumps({"rev": "ok", "quick": True, "grids": {"g": {}}})
    hist.write_text(good + "\n{truncated", encoding="utf-8")
    assert perf.latest_history(str(hist))["rev"] == "ok"
    # quick filter: a full-suite entry never gates a quick run
    full = json.dumps({"rev": "full", "quick": False, "grids": {"g": {}}})
    hist.write_text(good + "\n" + full + "\n")
    assert perf.latest_history(str(hist), quick=True)["rev"] == "ok"
    assert perf.latest_history(str(hist), quick=False)["rev"] == "full"


def test_grid_builders_produce_workloads():
    """Every canonical grid lowers to nonempty same-pool workloads (cheap
    structural check; actual measurement runs in CI perf-smoke)."""
    for name, build in perf.GRIDS.items():
        wls, seeds, mode, horizon = build(quick=True)
        assert wls and seeds and horizon > 0, name
        assert len({w.num_pools for w in wls}) == 1, name
