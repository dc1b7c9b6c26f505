"""Chunked early-exit cycle loop pins (``SimEngine(chunk=K)``).

The while-of-scan-chunks loop checks ``all_done`` every K cycles instead
of every cycle; because the exit predicate is monotone and the carry is
frozen per cycle once it fires, results are *cycle-exact* for any K —
including the completion cycle (makespan), which must not round up to a
chunk boundary.  K=1 is the cycle-granular reference loop itself
(trace-counter-pinned below, not just result-pinned).
"""

import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine
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


def test_chunk_one_is_the_reference_loop():
    """K=1 must be bit-identical to the default engine AND trace the same
    number of times — it dispatches the very same while_loop core."""
    wl = _a2a_workload()
    ref = SimEngine(SMALL, mode="omniwar")
    k1 = SimEngine(SMALL, mode="omniwar", chunk=1)
    r_ref = ref.run(wl, seed=4, horizon=HORIZON)
    r_k1 = k1.run(wl, seed=4, horizon=HORIZON)
    assert r_ref == r_k1
    assert k1.trace_count == ref.trace_count == 1


@pytest.mark.parametrize("K", [4, 7, 64])
def test_chunked_loop_cycle_exact(K):
    """Any K reproduces the reference result exactly — in particular the
    makespan is the true completion cycle, not a multiple of K."""
    wl = _a2a_workload()
    ref = SimEngine(SMALL, mode="omniwar").run(wl, seed=9, horizon=HORIZON)
    rk = SimEngine(SMALL, mode="omniwar", chunk=K).run(
        wl, seed=9, horizon=HORIZON)
    assert rk == ref
    assert rk.completed  # the exit fired mid-horizon, not at the clamp


def test_chunked_loop_with_faults_and_telemetry():
    """Telemetry accumulators are part of the frozen carry: past the
    completion cycle the in-chunk tail must not keep accumulating."""
    lok = random_link_faults(SMALL, 0.1, seed=3)
    wl = _a2a_workload(link_ok=lok)
    spec = TelemetrySpec(window=64, n_windows=8)
    ref = SimEngine(SMALL, mode="omniwar", num_pools=wl.num_pools,
                    telemetry=spec)
    chunked = SimEngine(SMALL, mode="omniwar", num_pools=wl.num_pools,
                        telemetry=spec, chunk=32)
    a = ref.run(wl, seed=2, horizon=HORIZON)
    b = chunked.run(wl, seed=2, horizon=HORIZON)
    assert a == b
    import numpy as np
    for f in ("link_util", "vc_occ", "deroutes", "cycles", "delivered"):
        assert np.array_equal(np.asarray(getattr(a.telemetry, f)),
                              np.asarray(getattr(b.telemetry, f))), f


def test_chunked_loop_horizon_clamp():
    """An incomplete run must stop at exactly `horizon` cycles even when
    the horizon is not a chunk multiple (the frozen-carry tail again)."""
    wl = _a2a_workload()
    horizon = 10  # far too small to complete; 10 % 7 != 0
    ref = SimEngine(SMALL, mode="omniwar").run(wl, seed=0, horizon=horizon)
    rk = SimEngine(SMALL, mode="omniwar", chunk=7).run(
        wl, seed=0, horizon=horizon)
    assert not rk.completed and rk == ref


def test_chunk_validates():
    with pytest.raises(ValueError):
        SimEngine(SMALL, mode="omniwar", chunk=0)
