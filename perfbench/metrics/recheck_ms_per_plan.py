"""Session and engine, host side: milliseconds per plan of the
``solve.recheck`` span (the event-exact host schedule of the winners,
their cost and energy, and the joint validation), summed over the
window's solves."""
from harness import spans


def read(w):
    return spans.ms_per_plan(w, "solve.recheck")
