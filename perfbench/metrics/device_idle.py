"""Device: the share of the window, in percent, in which no operation ran
on the device (one less the union of the trace's operation intervals over
the window)."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * (1.0 - w.busy_s() / (w.t1 - w.t0))
