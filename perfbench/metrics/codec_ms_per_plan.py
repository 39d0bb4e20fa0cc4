"""Front door: the HTTP codec's milliseconds per plan, the mean over
requests whose ``http.encode`` span ended in the window of their
``http.decode`` (JSON parse, request) plus ``http.encode`` (plan JSON with
its validation) spans. Both run on the event loop."""
from harness import spans


def read(w):
    dec = spans.per_request(w, "http.decode")
    per = [1e3 * (e["data"]["seconds"] + dec[t]["data"]["seconds"])
           for t, e in spans.per_request(w, "http.encode").items()
           if t in dec and w.t0 <= e["ts"] < w.t1]
    return sum(per) / len(per) if per else None
