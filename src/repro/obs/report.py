"""Fleet report generator: render a trace directory into tables + markdown.

    PYTHONPATH=src python -m repro.obs.report TRACE_DIR [--out DIR]

Consumes the ``events.jsonl`` + ``manifest.json`` a :mod:`repro.obs.trace`
run produced — no simulation is re-run — and renders:

  * ``report.md``          — manifest header + every summary table;
  * ``spans.csv``          — span aggregation (count / total / mean / max);
  * ``sched.csv``          — scheduler event counts + fragmentation /
                             utilization summary per stream;
  * ``link_heatmap.csv``   — per-(label, switch, port) network link load
                             from every ``sim.telemetry`` event (the
                             per-strategy heatmap data);
  * ``latency.csv``        — log2 ejection-latency histograms per label;
  * ``queue_occupancy.csv``— per-pool queue-occupancy histograms per label.

An ``engine.dispatch`` row of the span tables times device calls whole:
each span covers a dispatch's enqueue and the device run up to its last
output, and the compile too where its ``new_key`` is true.

Every table is also queryable in-process (:func:`span_rows`,
:func:`sched_rows`, :func:`telemetry_events`, :func:`link_heatmap_rows`,
:func:`hottest_links`) so examples and tests can consume the same data the
CLI renders.
"""

from __future__ import annotations

import argparse
import csv
import io
import json
import os
import sys


# ------------------------------------------------------------------ loading
def load_trace(trace_dir: str) -> tuple[dict, list[dict]]:
    """Read (manifest, events) from a trace directory.

    Unparsable JSONL lines are skipped (a crashed run may truncate the
    final line) — reports should degrade, not raise.
    """
    manifest: dict = {}
    mpath = os.path.join(trace_dir, "manifest.json")
    if os.path.exists(mpath):
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError):
            manifest = {}
    events: list[dict] = []
    epath = os.path.join(trace_dir, "events.jsonl")
    if os.path.exists(epath):
        with open(epath) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(ev, dict):
                    events.append(ev)
    return manifest, events


def split_runs(events: list[dict]) -> list[tuple[str, list[dict]]]:
    """Split an event list on ``trace.start`` boundaries.

    ``events.jsonl`` is opened in append mode, so re-``configure``-ing into
    the same directory aggregates several runs into one file; every table
    must be computed per run, not over the blended log.  Returns
    ``[(run_id, events), ...]`` in file order — a single-run file (or one
    with no ``trace.start`` at all) comes back as one group.
    """
    runs: list[tuple[str, list[dict]]] = []
    cur_id, cur = "", []
    for ev in events:
        if ev.get("name") == "trace.start":
            if cur:
                runs.append((cur_id, cur))
            cur_id = str(ev.get("run_id") or f"run{len(runs) + 1}")
            cur = []
        cur.append(ev)
    if cur:
        runs.append((cur_id, cur))
    return runs or [("", [])]


# ------------------------------------------------------------------- tables
def span_rows(events: list[dict]) -> list[dict]:
    """Aggregate span events by name: count, total/mean/max duration."""
    agg: dict[str, list[float]] = {}
    for ev in events:
        if ev.get("type") == "span" and "dur_s" in ev:
            agg.setdefault(ev["name"], []).append(float(ev["dur_s"]))
    rows = []
    for name in sorted(agg, key=lambda n: -sum(agg[n])):
        durs = agg[name]
        rows.append({
            "span": name, "count": len(durs),
            "total_s": round(sum(durs), 4),
            "mean_s": round(sum(durs) / len(durs), 4),
            "max_s": round(max(durs), 4),
        })
    return rows


def sched_rows(events: list[dict]) -> list[dict]:
    """Per-stream scheduler digest: event counts + fragmentation stats.

    Streams are keyed by the ``stream`` attribute the scheduler stamps on
    its events (strategy/policy label); events without one aggregate
    under ``"-"``.
    """
    streams: dict[str, dict] = {}

    def row(key):
        return streams.setdefault(key, {
            "stream": key, "arrived": 0, "started": 0, "backfilled": 0,
            "finished": 0, "migrations": 0, "requeues": 0, "failures": 0,
            "frag_mean": "", "frag_max": "", "utilization": "",
        })

    frags: dict[str, list[float]] = {}
    for ev in events:
        name = ev.get("name", "")
        if not name.startswith("sched."):
            continue
        key = str(ev.get("stream", "-"))
        r = row(key)
        kind = name.split(".", 1)[1]
        if kind == "arrive":
            r["arrived"] += 1
        elif kind == "start":
            r["started"] += 1
            if ev.get("backfilled"):
                r["backfilled"] += 1
        elif kind == "depart":
            r["finished"] += 1
        elif kind == "migrate":
            r["migrations"] += 1
        elif kind == "requeue":
            r["requeues"] += 1
        elif kind == "fail":
            r["failures"] += 1
        elif kind == "frag":
            frags.setdefault(key, []).append(float(ev.get("value", 0.0)))
        elif kind == "summary":
            for field in ("utilization", "frag_mean", "frag_max"):
                if field in ev:
                    r[field] = round(float(ev[field]), 4)
    for key, vals in frags.items():
        r = row(key)
        if r["frag_mean"] == "":
            r["frag_mean"] = round(sum(vals) / len(vals), 4)
        if r["frag_max"] == "":
            r["frag_max"] = round(max(vals), 4)
    return [streams[k] for k in sorted(streams)]


def telemetry_events(events: list[dict]) -> list[dict]:
    """The ``sim.telemetry`` digests, in emission order."""
    return [ev for ev in events if ev.get("type") == "telemetry"]


def utilization_rows(events: list[dict]) -> list[dict]:
    """One row per telemetry digest: headline utilization / behavior."""
    rows = []
    for ev in telemetry_events(events):
        rows.append({
            "label": ev.get("label", ""),
            "cycles": ev.get("cycles", 0),
            "util_mean": ev.get("util_mean", ""),
            "util_max": ev.get("util_max", ""),
            "dim_util": "|".join(str(u) for u in ev.get("dim_util", [])),
            "deroutes": ev.get("deroutes", 0),
            "escalations": ev.get("escalations", 0),
            "injected": ev.get("injected", 0),
            "delivered": ev.get("delivered", 0),
            "lat_mean": ev.get("lat_mean", ""),
        })
    return rows


def link_heatmap_rows(events: list[dict]) -> list[dict]:
    """Flatten every digest's top links into per-strategy heatmap data."""
    rows = []
    for ev in telemetry_events(events):
        for link in ev.get("top_links", []):
            rows.append({"label": ev.get("label", ""), **link})
    return rows


def hottest_links(source, k: int = 5) -> list[dict]:
    """Top-k hottest network links.

    ``source`` is either a host :class:`~repro.obs.probes.Telemetry`
    object (delegates to its accessor) or a ``sim.telemetry`` event dict
    (slices its recorded ``top_links``).
    """
    if hasattr(source, "hottest_links"):
        return source.hottest_links(k)
    return list(source.get("top_links", []))[:k]


def latency_rows(events: list[dict]) -> list[dict]:
    rows = []
    for ev in telemetry_events(events):
        for b, cnt in enumerate(ev.get("lat_hist", [])):
            rows.append({
                "label": ev.get("label", ""), "bin": b,
                "lat_lo": 2 ** b, "lat_hi": 2 ** (b + 1), "count": cnt,
            })
    return rows


def queue_occupancy_rows(events: list[dict]) -> list[dict]:
    rows = []
    for ev in telemetry_events(events):
        for pool, hist in enumerate(ev.get("occ_hist", [])):
            for occ, cnt in enumerate(hist):
                rows.append({
                    "label": ev.get("label", ""), "pool": pool,
                    "occupancy": occ, "samples": cnt,
                })
    return rows


# ---------------------------------------------------------------- rendering
def _csv_text(rows: list[dict]) -> str:
    out = io.StringIO()
    w = csv.DictWriter(out, fieldnames=list(rows[0].keys()))
    w.writeheader()
    for r in rows:
        w.writerow(r)
    return out.getvalue()


def _md_table(rows: list[dict]) -> str:
    if not rows:
        return "_no data_\n"
    cols = list(rows[0].keys())
    lines = ["| " + " | ".join(cols) + " |",
             "| " + " | ".join("---" for _ in cols) + " |"]
    for r in rows:
        lines.append("| " + " | ".join(str(r.get(c, "")) for c in cols) + " |")
    return "\n".join(lines) + "\n"


# public aliases: the dashboard and the event store reuse these builders
csv_text = _csv_text
md_table = _md_table


def _run_tables(events: list[dict], heading: str = "##") -> list[str]:
    """The per-run table sections (shared by single- and multi-run paths)."""
    parts = []
    tel = utilization_rows(events)
    if tel:
        parts.append(f"\n{heading} Link utilization (per strategy)\n")
        parts.append(_md_table(tel))
        parts.append(f"\n{heading}# Hottest links\n")
        hot = []
        for ev in telemetry_events(events):
            for link in hottest_links(ev, 5):
                hot.append({"label": ev.get("label", ""), **link})
        parts.append(_md_table(hot))
    sched = sched_rows(events)
    if sched:
        parts.append(f"\n{heading} Scheduler streams (fragmentation & churn)\n")
        parts.append(_md_table(sched))
    spans = span_rows(events)
    if spans:
        parts.append(f"\n{heading} Span timings\n")
        parts.append(_md_table(spans))
    return parts


def render_markdown(manifest: dict, events: list[dict]) -> str:
    """The full fleet report as markdown text.

    An append-mode trace directory may hold several runs; tables are split
    on ``trace.start`` boundaries and the run count is surfaced up front —
    a blended multi-run table would silently aggregate unrelated streams.
    """
    runs = split_runs(events)
    parts = ["# Run report\n"]
    if manifest:
        keys = ("run_id", "git_rev", "backend", "devices", "lane_backend",
                "jax", "config_hash")
        parts.append("## Manifest\n")
        parts.append(_md_table([{k: manifest.get(k, "") for k in keys}]))
    if len(runs) > 1:
        parts.append(f"\n## Runs ({len(runs)})\n")
        parts.append(_md_table([
            {"run": rid or f"run{i + 1}", "events": len(evs)}
            for i, (rid, evs) in enumerate(runs)
        ]))
        for i, (rid, evs) in enumerate(runs):
            parts.append(f"\n## Run {rid or f'run{i + 1}'}\n")
            parts.extend(_run_tables(evs, heading="###"))
    else:
        parts.extend(_run_tables(events))
    parts.append(f"\n_{len(events)} events across {len(runs)} run(s)._\n")
    return "\n".join(parts)


def write_report(trace_dir: str, out_dir: str | None = None) -> dict[str, str]:
    """Render every table for one trace directory; returns written paths."""
    out_dir = out_dir or os.path.join(trace_dir, "report")
    os.makedirs(out_dir, exist_ok=True)
    manifest, events = load_trace(trace_dir)
    runs = split_runs(events)
    written: dict[str, str] = {}

    def emit_csv(name, fn):
        if len(runs) > 1:  # split per run; a leading run column labels rows
            rows = [
                {"run": rid or f"run{i + 1}", **row}
                for i, (rid, evs) in enumerate(runs)
                for row in fn(evs)
            ]
        else:
            rows = fn(events)
        if not rows:
            return
        path = os.path.join(out_dir, f"{name}.csv")
        with open(path, "w", newline="") as f:
            f.write(_csv_text(rows))
        written[name] = path

    emit_csv("spans", span_rows)
    emit_csv("sched", sched_rows)
    emit_csv("utilization", utilization_rows)
    emit_csv("link_heatmap", link_heatmap_rows)
    emit_csv("latency", latency_rows)
    emit_csv("queue_occupancy", queue_occupancy_rows)
    md = os.path.join(out_dir, "report.md")
    with open(md, "w") as f:
        f.write(render_markdown(manifest, events))
    written["report"] = md
    return written


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("trace_dir", help="directory with events.jsonl")
    p.add_argument("--out", default=None,
                   help="output directory (default: TRACE_DIR/report)")
    args = p.parse_args(argv)
    if not os.path.exists(os.path.join(args.trace_dir, "events.jsonl")):
        print(f"# obs.report: no events.jsonl under {args.trace_dir}",
              file=sys.stderr)
        return 2
    written = write_report(args.trace_dir, args.out)
    for name, path in sorted(written.items()):
        print(f"# {name}: {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
