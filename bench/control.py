"""Read the control: the plain reference, with one guarantee broken, put in
the program's place, and compared by the cell's own check.

    python bench/control.py --workload table4_bg.omniwar --seeds 11 12 13

The control is the ``Control`` of the mix's kind (``bench/kinds/``); for
``sweep`` it breaks the link-rate guarantee the configuration states (an
output port's tokens are never charged, so a link may carry two packets
every packet-time).  Each seed makes one call's lanes from the control and
prints the check's numbers; the cell's limits must fail them.  The run
needs no accelerator: the control and the reference run on the host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import generator
import harness

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_cell(config: dict, mix: dict, seed: int):
    """The cell of ``mix`` with the control in the program's place."""
    return generator.load_kind(mix["kind"]).Control(config, mix, seed)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    _, _, config, mix = harness.load_cell(ROOT, args.workload)
    for seed in args.seeds:
        work = control_cell(config, mix, seed)
        work.setup()
        numbers, info = work.check([work.call(0)])
        print(json.dumps({"seed": seed, **info,
                          **{k: v for k, (v, _) in numbers.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    sys.exit(main())
