"""Host-side run tracing: structured span/event JSONL logs + run manifest.

One :class:`Tracer` owns a trace directory:

  * ``events.jsonl`` — one JSON object per line.  Every event carries
    ``t`` (seconds since the tracer started), ``type`` (``"event"`` |
    ``"span"`` | ``"counter"`` | ``"gauge"`` | ``"telemetry"``) and
    ``name``; spans add ``dur_s``; counters/gauges add ``value``; any
    extra keyword attributes ride along verbatim.
  * ``manifest.json`` — the run manifest: schema version, run id, git
    rev, jax version/backend/device count, engine ``lane_backend``,
    python/platform, caller extras, and a ``config_hash`` over all of it.

The module-level API (:func:`span`, :func:`event`, :func:`counter`,
:func:`gauge`) routes through one process-global tracer configured with
:func:`configure` and is **zero-cost when off**: with no tracer active,
``span`` returns one shared ``nullcontext`` singleton and the emitters
return immediately — the scheduler event loop pays a single global load
and a falsy check.

Two kinds of span, on two clocks:

  * :func:`span` writes a JSONL span timed on ``time.perf_counter``, and
    only while a tracer is active.
  * :func:`stage` is for the engine's host stages (``engine.prepare``,
    ``engine.stack``, ``engine.dispatch``, ``engine.to_result``).  It
    always enters ``jax.profiler.TraceAnnotation(name, **attrs)``, which
    puts the span and its attributes on the profiler's clock, beside the
    device's operations, whenever a profile is being captured; with no
    capture that costs about a microsecond and records nothing.  While a
    tracer is active it also writes the same JSONL span as ``span``.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import platform
import subprocess
import threading
import time

from jax.profiler import TraceAnnotation

SCHEMA = 1

_NULL = contextlib.nullcontext()
_tracer: "Tracer | None" = None


def _git_rev() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            capture_output=True, text=True, check=True, timeout=10,
        ).stdout.strip()
    except Exception:
        return "unknown"


def manifest_dict(**extra) -> dict:
    """The run manifest: host/backend provenance + caller extras.

    Also used standalone by ``benchmarks/perf.py`` so BENCH snapshots
    carry the same provenance block as trace directories.
    """
    import jax

    from repro.core.engine.runner import default_lane_backend

    info = {
        "schema": SCHEMA,
        "git_rev": _git_rev(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": jax.local_device_count(),
        "lane_backend": default_lane_backend(),
        "python": platform.python_version(),
        "platform": platform.platform(),
    }
    info.update(extra)
    blob = json.dumps(
        {k: v for k, v in sorted(info.items())}, sort_keys=True, default=str
    )
    info["config_hash"] = hashlib.sha256(blob.encode()).hexdigest()[:16]
    return info


def _json_default(o):
    item = getattr(o, "item", None)  # numpy scalars
    if callable(item):
        return item()
    if hasattr(o, "tolist"):
        return o.tolist()
    return str(o)


class Tracer:
    """Writes one run's event log + manifest under ``trace_dir``."""

    def __init__(self, trace_dir: str, run_id: str | None = None, **extra):
        os.makedirs(trace_dir, exist_ok=True)
        self.dir = trace_dir
        self.run_id = run_id or time.strftime("%Y%m%d-%H%M%S")
        self.path = os.path.join(trace_dir, "events.jsonl")
        self._f = open(self.path, "a", encoding="utf-8")
        self._closed = False
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self.manifest = manifest_dict(run_id=self.run_id, **extra)
        self._write_manifest()
        self.event("trace.start", run_id=self.run_id)

    def _write_manifest(self):
        with open(os.path.join(self.dir, "manifest.json"), "w") as f:
            json.dump(self.manifest, f, indent=2, sort_keys=True,
                      default=_json_default)
            f.write("\n")

    def annotate(self, **fields):
        """Merge late-bound fields (e.g. the realized lane_backend) into
        the manifest and rewrite it."""
        self.manifest.update(fields)
        self._write_manifest()

    # ------------------------------------------------------------ emitters
    def event(self, name: str, **attrs):
        ev = {"t": round(time.perf_counter() - self._t0, 6),
              "type": attrs.pop("type", "event"), "name": name}
        ev.update(attrs)
        line = json.dumps(ev, default=_json_default)
        with self._lock:
            # post-close emits are safe no-ops: an in-flight span() held
            # across disable()/configure() must not raise "I/O operation
            # on closed file" when it finally exits (regression-pinned)
            if self._closed:
                return
            self._f.write(line + "\n")
            self._f.flush()

    def counter(self, name: str, value, **attrs):
        self.event(name, type="counter", value=value, **attrs)

    def gauge(self, name: str, value, **attrs):
        self.event(name, type="gauge", value=value, **attrs)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.event(name, type="span",
                       dur_s=round(time.perf_counter() - t0, 6), **attrs)

    @property
    def closed(self) -> bool:
        return self._closed

    def close(self):
        if self._closed:
            return
        self.event("trace.end")
        with self._lock:
            self._closed = True
            self._f.close()


# ------------------------------------------------------- module-level API
def configure(trace_dir: str, run_id: str | None = None, **extra) -> Tracer:
    """Activate tracing into ``trace_dir`` (closing any previous tracer)."""
    global _tracer
    if _tracer is not None:
        _tracer.close()
    _tracer = Tracer(trace_dir, run_id=run_id, **extra)
    return _tracer


def disable():
    """Deactivate tracing (all module-level calls become no-ops again)."""
    global _tracer
    if _tracer is not None:
        _tracer.close()
    _tracer = None


def active() -> Tracer | None:
    return _tracer


def span(name: str, **attrs):
    """A timing span context manager; the shared no-op when tracing is off."""
    t = _tracer
    return _NULL if t is None else t.span(name, **attrs)


@contextlib.contextmanager
def stage(name: str, **attrs):
    """A span on the profiler's clock, and in the JSONL log when tracing.

    ``attrs`` ride on both: the profiler records them as the event's
    stats (numbers and strings; a bool reads back as 0/1).
    """
    t = _tracer
    with TraceAnnotation(name, **attrs):
        if t is None:
            yield
        else:
            with t.span(name, **attrs):
                yield


def event(name: str, **attrs):
    t = _tracer
    if t is not None:
        t.event(name, **attrs)


def counter(name: str, value, **attrs):
    t = _tracer
    if t is not None:
        t.counter(name, value, **attrs)


def gauge(name: str, value, **attrs):
    t = _tracer
    if t is not None:
        t.gauge(name, value, **attrs)


def log_telemetry(label: str, telemetry, **attrs):
    """Emit a compact ``sim.telemetry`` event from a host Telemetry view."""
    t = _tracer
    if t is not None and telemetry is not None:
        t.event("sim.telemetry", type="telemetry",
                **telemetry.summary(label), **attrs)
