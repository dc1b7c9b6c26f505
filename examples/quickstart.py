"""Quickstart: the paper's resource allocation machinery in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py
"""

from repro.core.hyperx import HyperX
from repro.core.allocation import allocate_partition, machine_partitions
from repro.core.properties import analyze_partition
from repro import traffic as tr
from repro.core.engine import SimEngine
from repro.fabric.placement import place_job
from repro.fabric.collective_model import CollectiveModel
from repro.route import apply_faults, fail_links
from repro.sched import Job, OnlineScheduler
from repro.traffic import AppSpec, PhaseSpec, ScenarioSpec, build_workload


def main():
    # 1) the paper machine: 8x8 HyperX, 8 endpoints/switch
    topo = HyperX(n=8, q=2)
    print(f"machine: {topo} — {topo.num_endpoints} endpoints, "
          f"{topo.num_links} links, diameter {topo.diameter}")

    # 2) allocate one 64-rank job under two strategies and compare (Table 1)
    strategies = ("row", "diagonal")
    for strat in strategies:
        part = allocate_partition(strat, topo, 0)
        p = analyze_partition(topo, part)
        print(f"{strat:10s} avg_dist={p.avg_distance:.3f} "
              f"convex={p.convexity:13s} PB={p.partition_bandwidth:.2f}")

    # 3) simulate an All-to-All on each allocation (the paper's evaluation).
    # Both scenarios share one compilation and run as ONE batched device
    # call: the engine takes workload tables as vmapped pytree arguments.
    engine = SimEngine(topo, mode="omniwar")
    workloads = []
    for strat in strategies:
        parts = machine_partitions(strat, topo, num_jobs=8)
        workloads.append(
            tr.compose_workload(topo, [(tr.all_to_all(64), p) for p in parts])
        )
    results = engine.run_grid(workloads, horizon=40000)
    for strat, (res,) in zip(strategies, results):
        print(f"{strat:10s} 8x all-to-all makespan = "
              f"{res.makespan_cycles} cycles (avg hops {res.avg_hops:.2f})")

    # 4) the framework side: place a 256-chip training mesh by strategy and
    # price its collectives with the partition-bandwidth cost model
    for strat in ("rectangular", "diagonal"):
        placement = place_job(strat, (16, 16), ("data", "model"))
        model = CollectiveModel(placement)
        c = model.cost("all_reduce", "data", 64e6)
        print(f"{strat:12s} data-axis PB={c.pb:5.2f} -> "
              f"64MB grad all-reduce {c.total_s*1e3:.2f} ms")

    # 5) online scheduling: two jobs contend for the machine.  Job B needs
    # 4 base blocks while job A holds 6 of the 8, so B queues until A
    # departs — the scheduler reports its wait, the fragmentation it saw,
    # and the realized PB of the partitions actually placed.
    print("\ntwo-job stream, Diagonal vs Rectangular:")
    jobs = [
        Job(job_id=0, arrival=0.0, blocks=6, service=30.0),
        Job(job_id=1, arrival=5.0, blocks=4, service=20.0),
    ]
    for strat in ("diagonal", "rectangular"):
        res = OnlineScheduler(topo, strategy=strat).run_stream(jobs)
        s = res.summary()
        waits = {r.job_id: r.wait for r in res.records}
        print(f"{strat:12s} waits={{A: {waits[0]:.0f}, B: {waits[1]:.0f}}} "
              f"frag_mean={s['frag_mean']:.3f} util={s['utilization']:.2f} "
              f"realized_PB={s['realized_pb_mean']:.2f}")

    # 6) fault-aware routing: the same Diagonal-vs-Rectangular comparison
    # under UGAL with one dead cable.  The mask rides in the workload
    # tables, so both strategies (and the fault) share one compilation
    # and one batched device call; routing steers around the dead link.
    print("\n64-rank all-to-all under ugal, one failed link (0 <-> 1):")
    ugal = SimEngine(topo, mode="ugal")
    mask = fail_links(topo, [(0, 1)])
    faulty = [
        apply_faults(
            tr.compose_workload(
                topo, [(tr.all_to_all(64), allocate_partition(strat, topo, 0))]
            ),
            mask,
        )
        for strat in ("diagonal", "rectangular")
    ]
    for strat, (res,) in zip(("diagonal", "rectangular"),
                             ugal.run_grid(faulty, horizon=40000)):
        print(f"{strat:12s} makespan = {res.makespan_cycles} cycles "
              f"(avg hops {res.avg_hops:.2f}, max hops {res.max_hops} "
              f"< VC budget {ugal.static.V})")

    # 7) declarative phased scenarios: the canonical HPC iteration —
    # stencil compute-exchange rounds followed by an all-reduce — as ONE
    # app built through the traffic-pattern registry (repro.traffic).
    # Both strategies again share one compilation and one device call.
    print("\nphased stencil+all-reduce job, Diagonal vs Rectangular:")
    engine = SimEngine(topo, mode="omniwar")
    phased = [
        build_workload(topo, ScenarioSpec(apps=(
            AppSpec(
                phases=(PhaseSpec("stencil_von_neumann", {"rounds": 8}),
                        PhaseSpec("all_reduce", {"vector_packets": 64})),
                placement=strat,
            ),
        )))
        for strat in ("diagonal", "rectangular")
    ]
    for strat, (res,) in zip(("diagonal", "rectangular"),
                             engine.run_grid(phased, horizon=40000)):
        print(f"{strat:12s} stencil+all_reduce makespan = "
              f"{res.makespan_cycles} cycles (avg hops {res.avg_hops:.2f})")


if __name__ == "__main__":
    main()
