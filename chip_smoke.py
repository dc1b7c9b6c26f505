"""Chip smoke run: the simulator's main path on a TPU at the paper's size.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # lanes across four chips only

One chip: the Table 4 all-to-all grid (all seven allocation strategies,
isolated and under the random-permutation background, omniwar routing,
2 seeds, horizon 80,000) on the 8x8 paper HyperX, through
``benchmarks.common.sweep`` -> ``SimEngine.run_grid``.  It checks, on the
chip, that

  * a repeat of the same lanes traces nothing new;
  * every lane conserves packets (``injected == ejected + stranded``,
    ``sum(epoch_delivered) == delivered``) and completes;
  * Diagonal and Rectangular with background, seed 0, equal a CPU
    reference computed in this process;
  * ``arb="pallas"`` and ``kernel="pallas"`` engines equal the lax engine
    on those lanes, and their compiled programs hold ``tpu_custom_call``
    (the kernels were compiled by Mosaic, not interpreted).

``--chips 4`` runs only the same grid with ``shard_map`` lanes across
four chips, compared with per-lane ``run`` on one device.

There is no CPU fallback: the run exits non-zero unless JAX's first
device is a TPU.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# the CPU reference needs the CPU backend beside the TPU
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402

ROOT = os.path.dirname(os.path.abspath(__file__))
HORIZON = 80_000
SEEDS = (0, 1)
MODE = "omniwar"
KIND = "all_to_all"
# the lanes checked against the CPU reference and the Pallas engines
CHECKED = (("diagonal", True), ("rectangular", True))


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (first device is {dev.platform}); "
                 "there is no CPU fallback")
    return dev


class Checks:
    """Collects failed checks; the run fails if any did."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str):
        print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()  # SimResults are host values: device work is done
    return out, time.perf_counter() - t0


def grid_workloads():
    from benchmarks.common import STRATEGIES, interference_workload

    cells = [(s, bg) for bg in (False, True) for s in STRATEGIES]
    return cells, [interference_workload(s, KIND, with_bg=bg)
                   for s, bg in cells]


def compiled_core(engine, wls, n_seeds):
    """The engine's 1-device grid executable for ``wls`` x ``n_seeds``
    (one shape bucket)."""
    import jax.numpy as jnp
    from repro.core.engine import stack_tables

    stacked = stack_tables([engine.prepare(w).tables for w in wls])
    seeds = jnp.zeros(n_seeds, jnp.int32)
    return engine._runNS.lower(stacked, seeds, jnp.int32(HORIZON)).compile()


def one_chip(check: Checks):
    from benchmarks.common import PAPER_TOPO, summarize, sweep
    from repro.core.engine import SimEngine, get_engine

    cells, wls = grid_workloads()
    engine = get_engine(PAPER_TOPO, mode=MODE, num_pools=wls[0].num_pools)
    check(all(w.num_pools == engine.num_pools for w in wls),
          "one engine serves the whole grid")
    buckets = sorted({engine.prepare(w).tables.shape_bucket for w in wls})
    print(f"grid: {len(wls)} workloads x {len(SEEDS)} seeds = "
          f"{len(wls) * len(SEEDS)} lanes, buckets {buckets}", flush=True)

    def run():
        return sweep(wls, mode=MODE, horizon=HORIZON, seeds=SEEDS,
                     topo=PAPER_TOPO)

    grid, first_s = timed(run)
    traces = engine.trace_count
    again, repeat_s = timed(run)
    print(f"wall: first call {first_s:.3f} s (compile included), repeat "
          f"{repeat_s:.3f} s; traces {traces}, device calls "
          f"{engine.device_calls}", flush=True)
    check(engine.trace_count == traces, "repeat call traces nothing new")
    check(again == grid, "repeat call gives the same results")

    for bucket in buckets:
        same = [w for w in wls
                if engine.prepare(w).tables.shape_bucket == bucket]
        mem = compiled_core(engine, same, len(SEEDS)).memory_analysis()
        print(f"memory_analysis (bucket {bucket}, {len(same)}x{len(SEEDS)} "
              f"lanes): arguments {mem.argument_size_in_bytes} B, outputs "
              f"{mem.output_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, code "
              f"{mem.generated_code_size_in_bytes} B", flush=True)

    lanes = [r for per_seed in grid for r in per_seed]
    check(all(r.injected == r.ejected + r.stranded for r in lanes),
          "conservation: injected == ejected + stranded on every lane")
    check(all(sum(r.epoch_delivered) == r.delivered for r in lanes),
          "conservation: sum(epoch_delivered) == delivered on every lane")
    check(all(r.completed for r in lanes), "every lane completes")

    for (strategy, bg), per_seed in zip(cells, grid):
        s = summarize(per_seed)
        print(f"makespan {strategy:>15} {'bg ' if bg else 'iso'} "
              f"{s['makespan']} (seeds {[r.makespan for r in per_seed]})")
    by_cell = dict(zip(cells, grid))
    for bg in (False, True):
        d = summarize(by_cell[("diagonal", bg)])["makespan"]
        r = summarize(by_cell[("rectangular", bg)])["makespan"]
        print(f"diagonal vs rectangular ({'bg' if bg else 'isolated'}): "
              f"{d} vs {r}", flush=True)

    # lanes checked against the CPU reference and the Pallas engines
    checked = [wls[cells.index(c)] for c in CHECKED]
    on_chip = [by_cell[c][0] for c in CHECKED]
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref_engine = SimEngine(PAPER_TOPO, mode=MODE,
                               num_pools=engine.num_pools)
        ref, ref_s = timed(lambda: ref_engine.run_grid(
            checked, seeds=(0,), horizon=HORIZON))
    print(f"cpu reference: {ref_s:.3f} s", flush=True)
    check([r[0] for r in ref] == on_chip,
          "chip equals the CPU reference (diagonal, rectangular; bg, seed 0)")

    for knob in ({"arb": "pallas"}, {"kernel": "pallas"}):
        name = ",".join(f"{k}={v}" for k, v in knob.items())
        eng = SimEngine(PAPER_TOPO, mode=MODE, num_pools=engine.num_pools,
                        **knob)
        res, wall = timed(lambda: eng.run_grid(checked, seeds=(0,),
                                               horizon=HORIZON))
        print(f"{name}: first call {wall:.3f} s", flush=True)
        check([r[0] for r in res] == on_chip, f"{name} equals lax on chip")
        text = compiled_core(eng, checked, 1).as_text()
        check("tpu_custom_call" in text,
              f"{name} compiled program holds tpu_custom_call")


def four_chips(check: Checks):
    from benchmarks.common import PAPER_TOPO
    from repro.core.engine import SimEngine

    check(jax.local_device_count() == 4,
          f"4 local devices (have {jax.local_device_count()})")
    _, wls = grid_workloads()
    engine = SimEngine(PAPER_TOPO, mode=MODE, num_pools=wls[0].num_pools)
    grid, first_s = timed(lambda: engine.run_grid(wls, seeds=SEEDS,
                                                  horizon=HORIZON))
    print(f"run_grid on {jax.local_device_count()} devices "
          f"({engine.lane_backend}): first call {first_s:.3f} s", flush=True)
    _, repeat_s = timed(lambda: engine.run_grid(wls, seeds=SEEDS,
                                                horizon=HORIZON))
    print(f"run_grid repeat {repeat_s:.3f} s", flush=True)
    check(engine.lane_backend == "shard_map", "lanes dispatched by shard_map")
    ref, ref_s = timed(lambda: [
        [engine.run(wl, seed=s, horizon=HORIZON) for s in SEEDS]
        for wl in wls])
    print(f"per-lane run on one device: {ref_s:.3f} s", flush=True)
    check(grid == ref, "4-chip lanes equal per-lane run on one device")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the shard_map lanes across four chips")
    args = p.parse_args(argv)
    dev = require_tpu()
    print(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}",
          flush=True)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.core.engine import enable_persistent_cache

    print(f"compile cache: {enable_persistent_cache()}", flush=True)
    check = Checks()
    (four_chips if args.chips == 4 else one_chip)(check)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
