"""Session and engine, host side: the wall seconds of each served batch's
solve (the session's ``plan_solved`` span: packing, device arrays, the
device solve, the host's event-exact re-check) less the device-busy time
inside it, summed over the window's batches, per plan served."""


def read(w):
    if w.trace is None:
        return None
    solves = w.solves()
    plans = sum(s.n for s in solves)
    if not plans:
        return None
    host = sum(s.end - s.start - w.busy_s(s.start, s.end) for s in solves)
    return host / plans
