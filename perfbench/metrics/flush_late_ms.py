"""Front door: the 90th percentile, in milliseconds, of how late the
flush timer fired (``late_s`` of the window's ``wait`` and ``deadline``
flush events): the event loop's lag when a batch was due."""
from harness import stats


def read(w):
    late = [1e3 * e["data"]["late_s"] for e in w.of_type("flush")
            if w.t0 <= e["ts"] < w.t1 and "late_s" in e["data"]
            and e["data"].get("cause") in ("wait", "deadline")]
    return stats.percentile(late, 90) if late else None
