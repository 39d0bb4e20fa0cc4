"""Kernel: the ``sgs_decode`` kernel's share of its roofline, in percent:
the least time its calls require (the larger of required operations over
the chip's peak and required bytes over its memory bandwidth, from each
call's chains, slots, resources and grid bins; see
``harness.layers.decode_work``) over the time they took on the device.
Slots are the batch's real ones: the task count of its widest DAG, times
the batch's bucket in a joint (shared) decode."""
from harness import layers


def read(w):
    if w.trace is None:
        return None
    need = took = 0.0
    for s in w.solves():
        for op in w.kernel_ops(s.start, s.end):
            chains, padded, M = layers.call_shape(op)
            J = s.jmax * (s.bucket if w.shared else 1) or padded
            t, _ = layers.least_time(*layers.decode_work(
                chains, min(J, padded), M, w.grid), w.peaks)
            need += t
            took += op.end - op.start
    return 100.0 * need / took if took else None
