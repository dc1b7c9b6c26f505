"""The one traffic generator: a mix's parameters -> the calls of a cell.

A traffic mix (``bench/traffic/<name>.json``) names its ``kind`` and
parameters; a configuration (``bench/configs/<name>.json``) names the
machine, the routing and the engine.  ``build`` finds the kind's module,
``bench/kinds/<kind>.py``, by name, and pairs the two into a cell object
with four steps, which the harness drives:

  * ``setup()``   -- build the inputs through the program's public API and
    warm every compile key the window will use; returns facts to print;
  * ``call(i)``   -- the i-th timed call; returns a :class:`CallRecord`;
  * ``release()`` -- drop the program's state once the window is over;
  * ``check(calls)`` -- compare what the window produced with the plain
    reference (``bench/reference``); returns ``({name: (value, limit)},
    facts)``.

A kind module defines ``Cell(config, mix, seed)`` and ``Control(config,
mix, seed)``: the same cell with the plain reference, one guarantee
broken, answering in the program's place (``bench/control.py``).  A kind
refuses a mix or a configuration key that it or the reference does not
model, so no setting is silently ignored.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

KINDS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kinds")

# SimResult fields the reference recomputes; all are compared exactly
FIELDS = (
    "makespan", "makespan_cycles", "delivered", "injected", "avg_latency",
    "avg_hops", "completed", "max_hops", "reescalated", "stranded",
    "ejected", "epoch_delivered", "epoch_injected",
)


@dataclasses.dataclass
class CallRecord:
    index: int
    lanes: int             # lanes the call computed
    own_cycles: int        # sum over lanes of each lane's own cycles
    iterations: int        # loop iterations of the batch (slowest lane)
    failed: int            # lanes that did not finish within the horizon
    answers: list          # per lane: (lane key, answer)
    start: float = 0.0     # host clock, set by the harness
    end: float = 0.0


def load_kind(kind: str):
    """The module ``bench/kinds/<kind>.py``."""
    path = os.path.join(KINDS, f"{kind}.py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(KINDS) if f.endswith(".py"))
        raise ValueError(f"unknown traffic kind {kind!r}; known: {known}")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(config: dict, mix: dict, seed: int):
    return load_kind(mix["kind"]).Cell(config, mix, seed)


def refuse_unknown(what: str, given: dict, known: set) -> None:
    """Raise on keys of ``given`` outside ``known``."""
    extra = sorted(set(given) - known)
    if extra:
        raise ValueError(f"{what}: keys {extra} are not modelled "
                         f"(known: {sorted(known)})")


def fields_of(answer) -> dict:
    """The compared fields of a program ``SimResult`` or a reference dict."""
    if isinstance(answer, dict):
        return {k: answer[k] for k in FIELDS}
    return {k: getattr(answer, k) for k in FIELDS}


def mismatches(got: dict, want: dict) -> int:
    return sum(got[k] != want[k] for k in FIELDS)
