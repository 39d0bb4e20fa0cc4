"""Host spans: what one batch's solve or one request's codec spent where.

A span is ``(name, start, end)`` on ``time.monotonic()`` — the clock the
session's solver events already use, and the one a profiler trace is
mapped onto through a marker span — so a span on the event tape lines up
with the device's operations. Spans are wall time even in a warped
replay: they never read ``DaemonConfig.clock``.

A ``Spans`` recorder belongs to one unit of work (one batch's solve, one
HTTP request) and is driven by the one thread doing that work, so spans
of the executor thread and of the event loop never mix. Its spans are
contiguous: ``mark()`` starts the first, each ``lap(name)`` ends the
current one and starts the next, one clock read per boundary.

The disabled recorder ``NULL_SPANS`` is falsy, and sites guard with
``if spans:`` exactly as emission sites guard with ``if sink:`` — off,
the cost is one truthiness check: no clock read, no ``Event``.

A finished span travels as one ``span`` event (schema v3): ``ts`` is its
end, ``data`` holds ``name``, ``seconds`` and the ``trace_ids`` of the
requests it served (the ``plan_solved`` convention). Pure stdlib, like
the rest of ``repro.obs``.
"""
from __future__ import annotations

import time
from typing import Iterable, List, Optional, Tuple

from repro.obs.events import SPAN, Event

# span vocabulary (docs/events.md): the served solve, executor thread —
SOLVE_PREPARE = "solve.prepare"    # flatten + reference point per request
SOLVE_REFPOINT = "solve.refpoint"  # shared pools: joint reference point
SOLVE_PACK = "solve.pack"          # pack, device arrays, initial chains
SOLVE_DEVICE = "solve.device"      # device solve through its blocking fetch
SOLVE_SELECT = "solve.select"      # shared pools: pick one of two assemblies
SOLVE_RECHECK = "solve.recheck"    # event-exact host re-check of the winners
# — and the HTTP codec, event-loop thread
HTTP_DECODE = "http.decode"        # json.loads + request_from_json
HTTP_ENCODE = "http.encode"        # plan_result_to_json (validate) + dumps


class Spans:
    """Contiguous ``(name, start, end)`` spans of one unit of work."""

    __slots__ = ("spans", "_t")

    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []
        self._t = 0.0

    def __bool__(self) -> bool:
        return True

    def mark(self) -> None:
        """Start the next span now."""
        self._t = time.monotonic()

    def lap(self, name: str) -> None:
        """End the span begun at the last ``mark``/``lap`` as ``name``,
        and start the next one at the same instant."""
        t = time.monotonic()
        self.spans.append((name, self._t, t))
        self._t = t

    def events(self, *, trace_ids: Iterable[str] = (),
               pool: Optional[str] = None) -> List[Event]:
        """One ``span`` event per recorded span, in order."""
        ids = [t for t in trace_ids if t]
        return [Event(SPAN, ts=end, pool=pool,
                      data={"name": name, "seconds": end - start,
                            "trace_ids": ids})
                for name, start, end in self.spans]


class NullSpans(Spans):
    """The falsy recorder: guarded sites skip it, and an unguarded call
    still reads no clock and records nothing."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def mark(self) -> None:
        pass

    def lap(self, name: str) -> None:
        pass


NULL_SPANS = NullSpans()


def recorder(enabled) -> Spans:
    """A fresh recorder when ``enabled`` (a sink, a flag) is truthy, else
    the shared falsy ``NULL_SPANS``."""
    return Spans() if enabled else NULL_SPANS
