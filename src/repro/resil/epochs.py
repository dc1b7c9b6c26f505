"""Epoch-structured fault schedules: the engine's time-varying fault axis.

A :class:`FaultSchedule` is the lowered form every fault process reduces
to: ``epoch_start`` (cycle each epoch begins; epoch 0 starts at cycle 0)
and one ``(S, q*n)`` directed-link health mask per epoch (see
:mod:`repro.route.faults` for the mask layout).  The schedule travels on
``Workload.fault_schedule`` into ``WorkloadTables`` — padded to a
power-of-two epoch count so fault grids still batch one-compile-one-call
per shape bucket — and the engine's cycle kernel switches masks
mid-flight with one gather on the current epoch index.  In-flight packets
survive a flip through the existing escalation / deroute machinery; what
strands anyway is counted by the new ``SimResult`` fields.

A one-epoch schedule is exactly a static mask: the engine's ``E = 1``
path is bit-identical to the pre-epoch kernel (trace-counter-pinned in
``tests/test_resil.py``).
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Sequence

import numpy as np

from repro.core.hyperx import HyperX
from repro.route import faults

if TYPE_CHECKING:  # pragma: no cover
    from repro.traffic.workload import Workload


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """Per-workload epoch schedule of directed-link health masks.

    ``epoch_start`` — (NE,) int64 cycle each epoch begins; must start at 0
    and be strictly increasing.  ``link_ok`` — (NE, S, q*n) bool, True =
    healthy.  Epoch ``e`` is active for cycles in
    ``[epoch_start[e], epoch_start[e+1])``; the last epoch runs forever.
    """

    epoch_start: np.ndarray
    link_ok: np.ndarray

    def __post_init__(self):
        starts = np.asarray(self.epoch_start, dtype=np.int64)
        masks = np.asarray(self.link_ok, dtype=bool)
        if starts.ndim != 1 or starts.size == 0:
            raise ValueError(f"epoch_start must be 1-D non-empty, got "
                             f"shape {starts.shape}")
        if masks.ndim != 3 or masks.shape[0] != starts.size:
            raise ValueError(
                f"link_ok must be (NE, S, q*n) with NE={starts.size}, "
                f"got shape {masks.shape}"
            )
        if starts[0] != 0:
            raise ValueError(f"epoch 0 must start at cycle 0, got {starts[0]}")
        if starts.size > 1 and not (np.diff(starts) > 0).all():
            raise ValueError(f"epoch starts must be strictly increasing: "
                             f"{starts.tolist()}")
        object.__setattr__(self, "epoch_start", starts)
        object.__setattr__(self, "link_ok", masks)

    @property
    def NE(self) -> int:
        return int(self.epoch_start.size)

    def epoch_at(self, t: int) -> int:
        """Index of the epoch active at cycle ``t``."""
        return int(np.searchsorted(self.epoch_start, t, side="right") - 1)

    def mask_at(self, t: int) -> np.ndarray:
        """The (S, q*n) mask active at cycle ``t``."""
        return self.link_ok[self.epoch_at(t)]


def static_schedule(
    topo: HyperX, link_ok: np.ndarray | None = None
) -> FaultSchedule:
    """One-epoch schedule — semantically identical to a static mask
    (and lowered to the engine's bit-identical ``E = 1`` path)."""
    mask = faults.no_faults(topo) if link_ok is None else link_ok
    return FaultSchedule(
        epoch_start=np.zeros(1, dtype=np.int64),
        link_ok=np.asarray(mask, dtype=bool)[None],
    )


def schedule_from_masks(
    topo: HyperX,
    entries: Sequence[tuple[int, np.ndarray]],
) -> FaultSchedule:
    """Build a schedule from ``(start_cycle, mask)`` pairs.

    Entries are sorted by start cycle; a healthy epoch 0 is prepended when
    the earliest entry starts after cycle 0, and duplicate start cycles
    keep the last-given mask (event-sourcing semantics).
    """
    if not entries:
        return static_schedule(topo)
    expect = (topo.num_switches, topo.q * topo.n)
    rows: dict[int, np.ndarray] = {}
    for start, mask in sorted(entries, key=lambda e: int(e[0])):
        mask = np.asarray(mask, dtype=bool)
        if mask.shape != expect:
            raise ValueError(
                f"mask shape {mask.shape} != {expect} for {topo}"
            )
        rows[int(start)] = mask
    if min(rows) > 0:
        rows = {0: faults.no_faults(topo), **rows}
    starts = np.asarray(sorted(rows), dtype=np.int64)
    return FaultSchedule(
        epoch_start=starts,
        link_ok=np.stack([rows[int(s)] for s in starts]),
    )


def apply_schedule(wl: "Workload", schedule: FaultSchedule) -> "Workload":
    """A copy of ``wl`` carrying the epoch schedule (lowered into the
    engine's ``WorkloadTables`` by the prepare step).  Composes with a
    static ``wl.link_ok`` mask: the engine ANDs both, so permanent faults
    plus dynamic churn stack."""
    expect = (wl.topo.num_switches, wl.topo.q * wl.topo.n)
    if schedule.link_ok.shape[1:] != expect:
        raise ValueError(
            f"schedule masks are {schedule.link_ok.shape[1:]}, "
            f"workload topology needs {expect}"
        )
    return dataclasses.replace(wl, fault_schedule=schedule)
