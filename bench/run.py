"""Run one benchmark cell once, on the accelerator of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json`` at the root of the checkout.  The last line of standard
output is one JSON object with the run's metrics, its device and its
correctness check.  There is no CPU fallback: without a TPU the run exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# the reference runs on the host CPU beside the accelerator
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

if __name__ == "__main__":
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import main

    sys.exit(main(sys.argv[1:], root=ROOT, t_start=T_START))
