"""Session and engine, host side: milliseconds per plan of the
``solve.prepare`` span (each request's DAG flattening and reference point,
before the batch's solve starts), summed over the window's solves."""
from harness import spans


def read(w):
    return spans.ms_per_plan(w, "solve.prepare")
