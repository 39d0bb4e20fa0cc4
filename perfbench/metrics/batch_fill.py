"""Front door: mean requests per flushed batch over the daemon's
``max_batch``, from the ``flush`` events of the window."""


def read(w):
    ns = [e["data"]["n"] for e in w.of_type("flush")
          if w.t0 <= e["ts"] < w.t1]
    return sum(ns) / len(ns) / w.max_batch if ns else None
