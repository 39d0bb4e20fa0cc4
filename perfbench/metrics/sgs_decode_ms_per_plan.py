"""Kernel: device milliseconds of the fused ``sgs_decode`` kernel per plan,
summed over the kernel's trace events inside the window's solves."""


def read(w):
    if w.trace is None:
        return None
    solves = w.solves()
    plans = sum(s.n for s in solves)
    secs = sum(o.end - o.start for s in solves
               for o in w.kernel_ops(s.start, s.end))
    return 1e3 * secs / plans if plans and secs else None
