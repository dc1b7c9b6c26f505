"""Device time of the cycle loop by stage, and the engine's host stages,
from a profiler trace of one ``run_grid`` call.

    python bench/stages.py --workload <cell> --seed <n> [--keep FILE]

on a machine with a TPU: sets the cell up as the harness does, traces one
whole call inside the benchmark's ``bench.window`` / ``bench.call`` spans,
and prints one JSON object (``--keep``: the trace, gzipped, too).

The program runs every operation of a simulated cycle under a
``jax.named_scope`` naming its stage (``core/engine/step.py``), and the
profiler tags each device operation with that scope path, the ``tf_op``
stat of the operation's event metadata (e.g. ``jit(core)/vmap(vmap())/
while/body/route/gather:``).  ``jax.profiler.ProfileData`` does not expose
that stat, and ``trace_reduce.Trace`` keeps none, so :func:`op_scopes`
reads it from the XSpace bytes with a protobuf wire reader.  An operation
of the cell's program, ``jit(core)``, belongs to the stage named right
after a ``/while/body/`` in its path; every other ``jit(core)`` operation
(the batched loop's per-lane carry selects, its condition, set-up,
outputs) to ``loop``, so the stages add up to the program's device time.
The host stages are the engine's ``engine.*`` spans, on the same clock.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import shutil
import sys
import tempfile

import numpy as np

STAGES = ("heads", "route", "arbitrate", "route_arbitrate", "dequeue",
          "deliver", "move", "complete", "inject", "telemetry")
ENGINE = ("engine.prepare", "engine.stack", "engine.dispatch",
          "engine.to_result")
PROGRAM = "jit(core)/"
_STAGE = re.compile(r"/while/body/(%s)[/:]" % "|".join(STAGES))


def _varint(b: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        shift += 7
        if x < 0x80:
            return out, i


def _fields(b: bytes, lo: int, hi: int):
    """The ``(field number, value)`` pairs of one protobuf message in
    ``b[lo:hi]``: an int for a varint, a ``(lo, hi)`` span for a
    length-delimited value; fixed-width values are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield field, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _entry(b: bytes, span: tuple) -> tuple:
    """``(key, value)`` of one protobuf map entry."""
    key = value = None
    for f, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_scopes(xspace: bytes) -> dict:
    """Device operation name -> its ``tf_op`` scope path, from the event
    metadata of every device plane.  Fields used: XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, stat_metadata = 5 (maps);
    XEventMetadata.name = 2, stats = 5; XStat.metadata_id = 1,
    str_value = 5, ref_value = 7; XStatMetadata.name = 2."""
    if xspace[:2] == b"\x1f\x8b":
        xspace = gzip.decompress(xspace)
    out = {}
    for f, plane in _fields(xspace, 0, len(xspace)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(xspace, *plane):
            if pf == 2:
                name = xspace[v[0]:v[1]].decode()
            elif pf == 4:
                events.append(_entry(xspace, v)[1])
            elif pf == 5:
                sid, meta = _entry(xspace, v)
                for mf, mv in _fields(xspace, *meta):
                    if mf == 2:
                        stat_names[sid] = xspace[mv[0]:mv[1]].decode()
        tf_op = [k for k, n in stat_names.items() if n == "tf_op"]
        if not name.startswith("/device:") or not tf_op:
            continue
        for span in events:
            op = scope = None
            for ef, ev in _fields(xspace, *span):
                if ef == 2:
                    op = xspace[ev[0]:ev[1]].decode()
                elif ef == 5:
                    sid = value = None
                    for sf, sv in _fields(xspace, *ev):
                        if sf == 1:
                            sid = sv
                        elif sf == 5:
                            value = xspace[sv[0]:sv[1]].decode()
                        elif sf == 7:
                            value = stat_names.get(sv)
                    if sid == tf_op[0]:
                        scope = value
            if op is not None and scope is not None:
                out.setdefault(op, scope)
    return out


def stage_of(scope: str) -> str | None:
    """The stage of a device operation's scope path: one of ``STAGES``,
    ``loop``, or ``None`` outside the program."""
    if not scope.startswith(PROGRAM):
        return None
    m = _STAGE.search(scope)
    return m.group(1) if m else "loop"


def stage_ns(trace, scopes: dict, lo: float, hi: float) -> dict:
    """Device time per stage in [lo, hi], averaged over the devices as
    ``Trace.busy_ns`` is (``trace``: a ``trace_reduce.Trace``)."""
    if len(trace.ops) == 0:
        return {}
    start = np.clip(trace.ops[:, 1], lo, hi)
    end = np.clip(trace.ops[:, 2], lo, hi)
    per = np.bincount(trace.ops[:, 0].astype(np.int64), weights=end - start,
                      minlength=len(trace.op_names))
    out: dict = {}
    for k in np.flatnonzero(per):
        s = stage_of(scopes.get(trace.op_names[k], ""))
        if s is not None:
            out[s] = out.get(s, 0.0) + float(per[k])
    ndev = max(len(trace.busy), 1)
    return {s: ns / ndev for s, ns in out.items()}


def breakdown(trace, scopes: dict, lane_iterations: int) -> dict:
    """Per traced ``bench.call``: device time per stage in us per
    lane-iteration, the loop's busy time on the same scale, and the
    engine's host stages in ms with the part of the call they leave
    uncovered."""
    import trace_reduce

    out = []
    for lo, hi in trace.spans("bench.call"):
        stages = stage_ns(trace, scopes, lo, hi)
        spans = {n: [(s, e) for s, e in trace.spans(n) if lo <= s and e <= hi]
                 for n in ENGINE}
        ivs = np.array([iv for v in spans.values() for iv in v],
                       dtype=np.float64).reshape(-1, 2)
        out.append({
            "call_ms": (hi - lo) / 1e6,
            "device_modules": int(sum(
                int(np.sum((iv[:, 0] >= lo) & (iv[:, 1] <= hi)))
                for iv in trace.busy.values())),
            "busy_us_per_lane_iteration":
                trace.busy_ns(lo, hi) / 1e3 / lane_iterations,
            "stage_us_per_lane_iteration": {
                s: ns / 1e3 / lane_iterations
                for s, ns in sorted(stages.items(), key=lambda kv: -kv[1])},
            "engine_ms": {n: sum(e - s for s, e in v) / 1e6
                          for n, v in spans.items()},
            "uncovered_ms":
                (hi - lo - trace_reduce.union_ns(ivs, lo, hi)) / 1e6,
        })
    return {"calls": out}


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--keep", default=None, metavar="FILE")
    args = p.parse_args(argv)
    bench = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(bench)
    sys.path[:0] = [bench, os.path.join(root, "src")]
    import jax

    import generator
    import harness
    import trace_reduce

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".bench_cache", "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _, cell, config, mix = harness.load_cell(root, args.workload)
    harness.require_accelerator(cell["chips"])
    work = generator.build(config, mix, args.seed)
    work.setup()
    capture = trace_reduce.Capture()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.call", index=0):
            rec = work.call(0)
    tmp = tempfile.mkdtemp(prefix="bench_stages_")
    keep = args.keep or os.path.join(tmp, "trace.xplane.pb.gz")
    try:
        trace = capture.stop(keep=keep)
        with open(keep, "rb") as f:
            scopes = op_scopes(f.read())
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    out = breakdown(trace, scopes, rec.lanes * rec.iterations)
    out.update(workload=args.workload, seed=args.seed, lanes=rec.lanes,
               iterations=rec.iterations,
               device=jax.devices()[0].device_kind)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
