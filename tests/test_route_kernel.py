"""Fused route+arbitrate megakernel parity pins.

``SimEngine(kernel="pallas")`` must reproduce the lax reference block in
``step.py`` bit for bit — the packed arbitration keys make every masked
min tie-free, so any drift is a bug, not noise.  Pinned here: all four
routing policies under faults (faults exercise the escalation candidate
sets and the reescalation counter), the batched grid path, and telemetry
probes (which tap g1/g2/best_min straight out of the fused block).
Off-TPU the kernel runs in Pallas interpret mode, so these pins run on
CPU CI (the ``kernel-parity`` CI step).
"""

import dataclasses

import numpy as np
import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine, make_fused_router
from repro.core.engine.tables import build_static_tables
from repro.core.hyperx import HyperX
from repro.obs.probes import TelemetrySpec
from repro.route import random_link_faults

SMALL = HyperX(n=4, q=2)
HORIZON = 5000


def _a2a_workload(strategy: str = "row", link_ok=None):
    part = allocate_partition(strategy, SMALL, 0)
    return tr.compose_workload(
        SMALL, [(tr.all_to_all(16), part)], link_ok=link_ok,
    )


def _telemetry_equal(a, b) -> bool:
    for f in a.__dataclass_fields__:
        if not np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))):
            return False
    return True


@pytest.mark.parametrize("mode", ["min", "omniwar", "val", "ugal"])
def test_fused_kernel_bit_identical_under_faults(mode):
    """The headline pin: every routing policy, with dead links in the
    candidate sets (escalation/reserve paths live), bit-exact."""
    lok = random_link_faults(SMALL, 0.15, seed=7)
    wl = _a2a_workload(link_ok=lok)
    ref = SimEngine(SMALL, mode=mode, num_pools=wl.num_pools)
    fused = SimEngine(SMALL, mode=mode, num_pools=wl.num_pools,
                      kernel="pallas")
    assert fused.run(wl, seed=5, horizon=HORIZON) == ref.run(
        wl, seed=5, horizon=HORIZON)


def test_fused_kernel_bit_identical_batched():
    """Grid dispatch (vmapped cross product) through the fused kernel."""
    wls = [_a2a_workload(s) for s in ("row", "diagonal", "full_spread")]
    ref = SimEngine(SMALL, mode="omniwar")
    fused = SimEngine(SMALL, mode="omniwar", kernel="pallas")
    assert fused.run_grid(wls, seeds=(0, 7), horizon=HORIZON) == \
        ref.run_grid(wls, seeds=(0, 7), horizon=HORIZON)


def test_fused_kernel_bit_identical_with_telemetry():
    """Telemetry probes consume fused-kernel outputs (link grants, chosen
    minimality); every window accumulator must match the lax engine."""
    lok = random_link_faults(SMALL, 0.1, seed=3)
    wl = _a2a_workload(link_ok=lok)
    spec = TelemetrySpec(window=64, n_windows=8)
    ref = SimEngine(SMALL, mode="omniwar", num_pools=wl.num_pools,
                    telemetry=spec)
    fused = SimEngine(SMALL, mode="omniwar", num_pools=wl.num_pools,
                      telemetry=spec, kernel="pallas")
    a = ref.run(wl, seed=2, horizon=HORIZON)
    b = fused.run(wl, seed=2, horizon=HORIZON)
    assert a == b  # simulated fields
    assert dataclasses.is_dataclass(a.telemetry)
    assert _telemetry_equal(a.telemetry, b.telemetry)


def test_fused_kernel_composes_with_chunked_loop():
    """kernel="pallas" + chunk=K stack: still bit-exact vs the reference
    cycle-granular lax engine."""
    lok = random_link_faults(SMALL, 0.15, seed=7)
    wl = _a2a_workload(link_ok=lok)
    ref = SimEngine(SMALL, mode="val", num_pools=wl.num_pools)
    fused = SimEngine(SMALL, mode="val", num_pools=wl.num_pools,
                      kernel="pallas", chunk=16)
    assert fused.run(wl, seed=9, horizon=HORIZON) == ref.run(
        wl, seed=9, horizon=HORIZON)


def test_make_fused_router_requires_switch_major_layout():
    st = build_static_tables(SMALL, mode="omniwar")
    fr = make_fused_router(st)
    assert callable(fr)
    bad = st._replace(H=st.H - 1)  # no longer divisible by S
    with pytest.raises(ValueError):
        make_fused_router(bad)
