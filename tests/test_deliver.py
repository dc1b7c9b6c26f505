"""Delivery accounting of the cycle kernel, pinned bit for bit.

A packet's ``sent`` and ``got`` rows depend only on its source rank and
its destination endpoint, so the kernel resolves both when the packet is
injected and carries them in its queue fields; ``deliver`` reads them at
the ring head and looks nothing up per head.  Pinned here: every
``SimResult`` field of a 4x4 machine running an all-to-all job under an
infinite-rate background, under omniwar, under val across two fault
epochs, and under min with the background in its own VC pool, equals the
values the kernel gave when ``deliver`` still looked the rows up from the
per-rank and per-endpoint tables at every ejection.
"""

import dataclasses

import numpy as np
import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine
from repro.core.hyperx import HyperX
from repro.resil import apply_schedule, scripted_campaign, to_epoch_schedule

SMALL = HyperX(n=4, q=2)

# name: (mode, fabric partitioning, fault script, pinned SimResult fields)
CASES = {
    "omniwar": ("omniwar", "shared", None, dict(
        makespan=39, makespan_cycles=624, delivered=240, injected=3861,
        avg_latency=9.554166666666667, avg_hops=1.9791666666666667,
        completed=True, max_hops=4, reescalated=0, stranded=544,
        ejected=3317, epoch_delivered=(240,), epoch_injected=(3861,))),
    "val_two_epochs": (
        "val", "shared",
        [(60, "fail", "link", (0, 1)), (60, "fail", "link", (5, 6))], dict(
            makespan=48, makespan_cycles=768, delivered=240, injected=3085,
            avg_latency=13.733333333333333, avg_hops=2.4625,
            completed=True, max_hops=5, reescalated=61, stranded=600,
            ejected=2485, epoch_delivered=(105, 135),
            epoch_injected=(2395, 690))),
    "min_background_pool": ("min", "background", None, dict(
        makespan=40, makespan_cycles=640, delivered=240, injected=3565,
        avg_latency=9.266666666666667, avg_hops=1.6, completed=True,
        max_hops=2, reescalated=0, stranded=483, ejected=3082,
        epoch_delivered=(240,), epoch_injected=(3565,))),
}


def _workload(fabric, faults):
    part = allocate_partition("diagonal", SMALL, 0)
    free = np.setdiff1d(np.arange(SMALL.num_endpoints), part.endpoints)
    bg = tr.background_noise(SMALL, free, seed=7)
    wl = tr.compose_workload(SMALL, [(tr.all_to_all(16), part)],
                             background=[bg], fabric_partitioning=fabric,
                             warmup=40)
    if faults:
        wl = apply_schedule(
            wl, to_epoch_schedule(SMALL, scripted_campaign(faults)))
    return wl


@pytest.mark.parametrize("name", list(CASES))
def test_results_equal_the_per_head_lookups_they_replace(name):
    mode, fabric, faults, pinned = CASES[name]
    wl = _workload(fabric, faults)
    engine = SimEngine(SMALL, mode=mode, num_pools=wl.num_pools)
    r = engine.run(wl, seed=5, horizon=20000)
    got = {f.name: getattr(r, f.name)
           for f in dataclasses.fields(r) if f.compare}
    assert got == pinned
