"""From a JAX profiler trace to the intervals the metric readers need.

A trace (an XSpace, ``*.xplane.pb``) holds one plane per device and one
for the host.  What is kept:

  * device busy intervals: the ``XLA Modules`` line of every
    ``/device:<kind>:<n>`` plane (a module is one program run on the
    device; the union of its intervals is the device's busy time);
  * device operations: the ``XLA Ops`` line, for the breakdown of where
    device time goes (control-flow wrappers such as ``while``, whose
    interval holds the other operations, are left out);
  * host events: every event of the host plane's lines, among them the
    benchmark's own ``bench.*`` spans (``jax.profiler.TraceAnnotation``)
    and, where the profiler's Python tracer ran, the program's functions.

All times are nanoseconds on the profiler's clock, shared by host and
device planes.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
import shutil
import tempfile

import numpy as np

_WRAPPER = re.compile(r"^%(while|conditional|call)[.\d]* ")
_OP = re.compile(r"^%(\S+) = ([a-z0-9]+\[[^\]]*\])?")


def short_op(name: str) -> str:
    """``%fusion.7 = s32[8]{0} fusion(...)`` -> ``fusion.7 s32[8]``."""
    m = _OP.match(name)
    if not m:
        return name[:80]
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def union_ns(intervals: np.ndarray, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` rows clipped to [lo, hi]."""
    if len(intervals) == 0:
        return 0.0
    iv = np.clip(intervals, lo, hi)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return float(total + cur_e - cur_s)


def gaps(intervals: np.ndarray, lo: float, hi: float) -> list[tuple]:
    """Idle ``(start, end)`` stretches of [lo, hi] outside every interval."""
    out, cur = [], lo
    if len(intervals):
        iv = np.clip(intervals, lo, hi)
        for s, e in iv[np.argsort(iv[:, 0], kind="stable")]:
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


@dataclasses.dataclass
class Trace:
    busy: dict            # device plane name -> (k, 2) module intervals
    op_names: list        # distinct device operation names
    ops: np.ndarray       # (k, 3) rows: name index, start, end
    host: list            # (name, start, end) of every host event

    @classmethod
    def from_bytes(cls, xspace: bytes) -> "Trace":
        from jax.profiler import ProfileData

        if xspace[:2] == b"\x1f\x8b":
            xspace = gzip.decompress(xspace)
        pd = ProfileData.from_serialized_xspace(xspace)
        busy, names, index, ops, host = {}, [], {}, [], []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        busy[plane.name] = np.array(
                            [(e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events], dtype=np.float64,
                        ).reshape(-1, 2)
                    elif line.name == "XLA Ops":
                        for e in line.events:
                            if _WRAPPER.match(e.name):
                                continue
                            k = index.setdefault(e.name, len(names))
                            if k == len(names):
                                names.append(e.name)
                            ops.append((k, e.start_ns,
                                        e.start_ns + e.duration_ns))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events)
        ops_arr = np.array(ops, dtype=np.float64).reshape(-1, 3)
        return cls(busy=busy, op_names=names, ops=ops_arr, host=host)

    # ---------------------------------------------------------- queries
    def spans(self, name: str) -> list[tuple[float, float]]:
        """Host intervals of the events called ``name``, in time order."""
        return sorted((s, e) for n, s, e in self.host if n == name)

    def busy_ns(self, lo: float, hi: float) -> float:
        """Device busy time in [lo, hi], averaged over the devices."""
        if not self.busy:
            return 0.0
        return float(np.mean([union_ns(iv, lo, hi)
                              for iv in self.busy.values()]))

    def top_ops(self, lo: float, hi: float, k: int = 10) -> list:
        """The ``k`` device operations with the most time in [lo, hi]."""
        if len(self.ops) == 0:
            return []
        start = np.clip(self.ops[:, 1], lo, hi)
        end = np.clip(self.ops[:, 2], lo, hi)
        per = np.bincount(self.ops[:, 0].astype(np.int64),
                          weights=end - start, minlength=len(self.op_names))
        order = np.argsort(-per, kind="stable")[:k]
        return [[short_op(self.op_names[i]), float(per[i]) / 1e9]
                for i in order if per[i] > 0]

    def host_at(self, t: float) -> str:
        """The innermost host event that covers time ``t``."""
        best, best_len = "host idle", np.inf
        for n, s, e in self.host:
            if s <= t <= e and e - s < best_len:
                best, best_len = n, e - s
        return best

    def idle_gaps(self, lo: float, hi: float, k: int = 10) -> list:
        """The ``k`` longest stretches of [lo, hi] in which no device was
        busy, each named by what the host was doing in its middle."""
        ivs = [iv for iv in self.busy.values() if len(iv)]
        allv = np.concatenate(ivs) if ivs else np.zeros((0, 2))
        gs = sorted(gaps(allv, lo, hi), key=lambda g: g[0] - g[1])[:k]
        return [[self.host_at((s + e) / 2), float(e - s) / 1e9]
                for s, e in gs]


class Capture:
    """Profile the host and devices from construction until ``stop``."""

    def __init__(self):
        import jax

        self._jax = jax
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._dir)

    def stop(self, keep: str | None = None) -> Trace:
        """Stop, read and delete the trace (``keep``: copy it there,
        gzipped, first)."""
        self._jax.profiler.stop_trace()
        try:
            path = sorted(glob.glob(os.path.join(
                self._dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
            with open(path, "rb") as f:
                data = f.read()
            if keep:
                with gzip.open(keep, "wb") as f:
                    f.write(data)
            return Trace.from_bytes(data)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
