"""Session and engine, host side: milliseconds per plan of the
``solve.refpoint`` span (a shared pool's joint reference point, which sets
the joint grid's resolution), summed over the window's solves. A program
without the span, or an isolated pool, gives no reading."""
from harness import spans


def read(w):
    return spans.ms_per_plan(w, "solve.refpoint")
