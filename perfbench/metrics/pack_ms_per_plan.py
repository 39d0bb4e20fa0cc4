"""Session and engine, host side: milliseconds per plan of the
``solve.pack`` span (packing the batch, the shared pool's joint reference
point, device arrays, initial chains), summed over the window's solves."""
from harness import spans


def read(w):
    return spans.ms_per_plan(w, "solve.pack")
