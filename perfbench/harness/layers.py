"""What the per-layer metric readers read: one measured window's event
tape (the planner's own spans and counters), its device trace, and the
required work of the ``sgs_decode`` kernel.

A reader (``perfbench/metrics/<name>.py``) defines ``read(window)`` and
returns a number, or ``None`` when the window holds nothing it can read.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from harness import stats
from harness.trace import DeviceTrace, Op, shapes

KERNEL = "sgs_decode"


@dataclasses.dataclass
class Solve:
    start: float                    # monotonic seconds
    end: float
    n: int                          # requests in the batch
    bucket: int
    jmax: int


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    events: List[dict]              # the planner's event tape, as JSON
    max_batch: int
    shared: bool                    # shared-capacity (joint) pool
    grid: int                       # time bins of the device decode
    peaks: Dict[str, float]
    trace: Optional[DeviceTrace] = None
    _memo: dict = dataclasses.field(default_factory=dict, repr=False)

    # -- the tape ------------------------------------------------------

    def of_type(self, kind: str) -> List[dict]:
        return [e for e in self.events if e["type"] == kind]

    def solves(self, clip: bool = True) -> List[Solve]:
        """Served batches whose solve began and ended inside the window
        (with ``clip``; all of the tape's otherwise).
        A ``plan_solved`` event is emitted as the session's solve returns
        and carries its wall ``seconds``; the ``cache_hit`` /
        ``bucket_traced`` event of the same dispatch carries its task
        shape."""
        shapes_by_ids = {}
        for e in self.events:
            if e["type"] in ("cache_hit", "bucket_traced"):
                ids = tuple(e["data"].get("trace_ids") or ())
                shapes_by_ids[ids] = e["data"].get("jmax", 0)
        out = []
        for e in self.of_type("plan_solved"):
            d = e["data"]
            if d.get("kind") != "plan":
                continue
            end = e["ts"]
            start = end - d["seconds"]
            if clip and (start < self.t0 or end > self.t1):
                continue
            jmax = shapes_by_ids.get(tuple(d.get("trace_ids") or ()), 0)
            out.append(Solve(start, end, int(d["n"]), int(d["bucket"]),
                             int(jmax)))
        return out

    # -- the device trace ----------------------------------------------

    def busy(self, device: Optional[int] = None
             ) -> List[Tuple[float, float]]:
        """Merged intervals in which an operation ran on ``device`` (on
        any device by default)."""
        key = ("busy", device)
        if key not in self._memo:
            self._memo[key] = stats.merge(
                [(o.start, o.end) for o in self.trace.ops
                 if device is None or o.device == device])
        return self._memo[key]

    def busy_s(self, lo: Optional[float] = None,
               hi: Optional[float] = None) -> float:
        """Seconds of [lo, hi) (the window by default) in which an
        operation ran, averaged over the devices."""
        lo = self.t0 if lo is None else lo
        hi = self.t1 if hi is None else hi
        per_dev = [stats.covered(self.busy(d), lo, hi)
                   for d in range(max(self.trace.devices, 1))]
        return sum(per_dev) / len(per_dev)

    def kernel_ops(self, lo: float, hi: float) -> List[Op]:
        if "kernel" not in self._memo:
            self._memo["kernel"] = [o for o in self.trace.ops
                                    if KERNEL in o.name]
        return [o for o in self._memo["kernel"]
                if lo <= o.start and o.end <= hi]


def decode_work(chains: int, J: int, M: int, T: int) -> Tuple[float, float]:
    """Operations and bytes one grid-SGS decode call requires, from its
    shape alone: per chain, J placements; each placement tests the M x T
    usage cells for overload (add, compare), tests the T candidate starts
    of the window (prefix sum, subtract, compare), selects among J slots
    (mask, max), and adds the task's demand to the M x T usage. Operands
    and results cross memory once: durations, priorities and M demands per
    slot in, release times, the J x J predecessor mask and M capacities in,
    start and finish (4 bytes) and a placed flag (1 byte) out."""
    ops = chains * J * (3.0 * M * T + 3.0 * T + 2.0 * J)
    bytes_ = 4.0 * (chains * J * (2 + M) + J + J * J + M) + chains * J * 9.0
    return ops, bytes_


def least_time(ops: float, bytes_: float, peaks: Dict[str, float]
               ) -> Tuple[float, str]:
    """(seconds, bound) of the roofline: the larger of operations over the
    peak rate and bytes over the memory bandwidth."""
    t_ops = ops / peaks["flops_per_s"]
    t_mem = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def call_shape(op: Op) -> Tuple[int, int, int]:
    """(chains, padded slots, resources) of one decode call, read from the
    shapes of its first result and its demand operand."""
    sh = shapes(op.text)
    out, dem = sh[0], sh[4]
    chains = 1
    for x in out[:-2]:
        chains *= x
    return chains, out[-1], dem[-2]
