"""Device: the share of the window, in percent, in which no operation ran
on the device while at least one request was in flight (between its
``submit`` event and the end of its ``http.encode`` span): idle time that
a request waited through, as opposed to idle time with nothing to do."""
from harness import spans


def read(w):
    if w.trace is None:
        return None
    flight = spans.in_flight(w)
    if not flight:
        return None
    idle = 0.0
    for a, b in flight:
        lo, hi = max(a, w.t0), min(b, w.t1)
        if hi > lo:
            idle += (hi - lo) - w.busy_s(lo, hi)
    return 100.0 * idle / (w.t1 - w.t0)
