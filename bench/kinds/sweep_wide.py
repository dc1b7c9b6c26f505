"""Kind ``sweep_wide``: the q-D interference sweep of kind ``sweep_qd`` on
a machine of 2**17 or more queue heads a lane.

The mix's keys, the closed loop, the set-up placement check and the
``heads`` on each call record are ``sweep_qd``'s.  What differs:

  * the reference machine may hold any number of heads that leaves the
    packed arbitration key ``MIN_RANDOM_BITS`` random bits
    (``reference/wide_key.py``), in place of ``sweep_qd``'s 17-bit
    refusal;
  * the check and the control simulate with that key: the 15-bit draw
    with its low ``head_bits - 17`` bits cleared (``wide_key.Draws``);
  * each call record also carries the program's head field width
    (``head_bits``, ``None`` where the program does not say) and the
    network port slots of a head's row (``net_ports`` = q * n), read by
    ``loop_ns_per_head_port_cycle.sweep_wide``.
"""

import dataclasses
import functools

import generator

qd = generator.load_kind("sweep_qd")


@dataclasses.dataclass
class CallRecord(qd.CallRecord):
    head_bits: int | None = None   # the program's head field width
    net_ports: int | None = None   # network port slots of a head's row


class Cell(qd.Cell):
    def reference_machine(self, config: dict):
        from reference import wide_key

        mc = qd.sweep.Cell.reference_machine(self, config)
        self.head_bits = wide_key.head_bits(mc.NQ)  # refuses past the floor
        return mc

    @functools.cached_property
    def wide_draws(self):
        from reference import wide_key

        return wide_key.Draws(self.mc.NQ, self.mc.QN, self.head_bits)

    def reference(self, key, break_link_rate: bool = False, draws=None):
        # the draws ``sweep`` hands in are the 17-bit key's; this machine's
        # key is the wide one
        return super().reference(key, break_link_rate,
                                 draws=self.wide_draws)

    def call(self, i: int) -> CallRecord:
        rec = super().call(i)
        fields = {f.name: getattr(rec, f.name)
                  for f in dataclasses.fields(rec)}
        return CallRecord(**fields,
                          head_bits=getattr(self.engine, "head_bits", None),
                          net_ports=self.mc.QN)


class Control(qd.Control, Cell):
    """``sweep``'s control (the link-rate guarantee broken) with the wide
    key."""
