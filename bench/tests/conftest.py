import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

CELL = "table4_bg.omniwar"


def make_root(path, config_changes=None, mix_changes=None) -> str:
    """A checkout-shaped directory at ``path`` whose ``table4_bg.omniwar``
    cell runs the cell's mix on a 4x4 HyperX: a 16-rank job, 40 warm-up
    cycles, and every lane of the window checked; then the given changes
    to the configuration and the mix."""
    import harness

    bench, cell, config, mix = harness.load_cell(ROOT, CELL)
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config["topology"] = {"n": 4, "q": 2, "concentration": 4}
    mix.update(ranks=16, warmup=40, check_lanes=len(mix["strategies"]))
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
