"""Paper Fig. 10 / Table 4: per-kernel interference (random-permutation
background), slowdown relative to Diagonal.

Per kernel, the full strategy grid (isolated + with-background workloads)
goes through one ``sweep`` call: the background grid shares one shape
bucket, so it executes as a single vmapped ``run_grid`` device call."""

from benchmarks.common import (
    STRATEGIES,
    emit,
    interference_workload,
    summarize,
    sweep,
)

KERNELS = ["all_to_all", "all_reduce", "stencil_von_neumann",
           "stencil_moore", "random_involution"]


def run(quick=False):
    kernels = KERNELS[:3] if quick else KERNELS
    raw = []
    for kind in kernels:
        iso_wls = [interference_workload(s, kind, with_bg=False)
                   for s in STRATEGIES]
        bg_wls = [interference_workload(s, kind, with_bg=True)
                  for s in STRATEGIES]
        per_wl = sweep(iso_wls + bg_wls, horizon=80000)
        iso_res, bg_res = per_wl[:len(STRATEGIES)], per_wl[len(STRATEGIES):]
        for strat, iso, bg in zip(STRATEGIES, iso_res, bg_res):
            iso_m = summarize(iso)["makespan"]
            bg_m = summarize(bg)["makespan"]
            raw.append({
                "kernel": kind, "strategy": strat,
                "iso": iso_m, "bg": bg_m,
                "extra": round(bg_m - iso_m, 1),
            })
    emit(raw, "fig10_kernel_interference_raw (paper Fig. 10)")
    rows = []
    sums = {s: [] for s in STRATEGIES}
    for kind in kernels:
        base = next(x["bg"] for x in raw
                    if x["strategy"] == "diagonal" and x["kernel"] == kind)
        for s in STRATEGIES:
            m = next(x["bg"] for x in raw
                     if x["strategy"] == s and x["kernel"] == kind)
            sums[s].append(base / max(m, 1))
    rows.append({s: round(sum(v) / len(v), 3) for s, v in sums.items()})
    emit(rows, "table4_interference_normalized (paper Table 4)")
    return rows


if __name__ == "__main__":
    run()
