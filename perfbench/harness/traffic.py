"""The one load generator: turns a traffic file into submissions.

Two loops, as a traffic file's ``loop`` says:

* ``open`` — independent submitters: requests fall due on a schedule
  whether or not earlier ones were answered. ``poisson`` arrivals at
  ``rate_per_s``: the window holds ``rate x seconds`` requests whose gaps
  are the exponential distribution's quantiles at (i + 0.5) / n, in one
  order drawn from the traffic file's ``arrival_seed``. The schedule is
  thus a property of the traffic file, as bursty as a Poisson process's,
  and the same in every run; a run's own seed orders the DAGs submitted.
* ``closed`` — ``outstanding`` callers that each submit their next DAG as
  soon as their plan returns. The callers start in groups of the daemon's
  ``max_batch``, ``stagger_s`` apart, so that each starting group fills one
  batch.

A request's latency runs from when it was due to when its plan JSON was
read in full; in the closed loop a request is due when it is sent.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import math
import time
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

from harness.client import post_plan


@dataclasses.dataclass
class Request:
    k: int
    dag: dict
    due: float                      # monotonic seconds
    sent: Optional[float] = None
    done: Optional[float] = None
    status: Optional[int] = None
    plan: Optional[dict] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.status == 200 and self.plan is not None

    @property
    def latency(self) -> float:
        return self.done - self.due if self.ok else math.inf


def poisson_gaps(rate: float, seconds: float,
                 arrival_seed: int) -> List[float]:
    n = max(int(rate * seconds), 1)
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    order = np.random.default_rng(arrival_seed).permutation(n)
    return [gaps[int(i)] for i in order]


def due_offsets(traffic: dict, seconds: float) -> List[float]:
    """Open loop: offsets from the window's start at which requests fall
    due, the first at 0, all inside the window."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    out, t = [], 0.0
    for g in poisson_gaps(float(traffic["rate_per_s"]), seconds,
                          int(traffic["arrival_seed"])):
        if t >= seconds:
            break
        out.append(t)
        t += g
    return out


class Generator:
    """Drives one window of a traffic file against ``host:port``."""

    def __init__(self, traffic: dict, dags: Iterator[dict], host: str,
                 port: int, max_batch: int,
                 clock: Callable[[], float] = time.monotonic):
        self.traffic = traffic
        self.dags = dags
        self.host, self.port = host, port
        self.max_batch = max_batch
        self.clock = clock
        self.requests: List[Request] = []
        self._bodies: Dict[str, bytes] = {}

    def _next(self, due: float) -> Request:
        dag = next(self.dags)
        req = Request(len(self.requests), dag, due)
        self.requests.append(req)
        return req

    def _body(self, dag: dict) -> bytes:
        body = self._bodies.get(dag["name"])
        if body is None:
            body = self._bodies[dag["name"]] = json.dumps(
                {"dag": dag}).encode()
        return body

    async def _send(self, req: Request) -> None:
        req.sent = self.clock()
        try:
            req.status, req.plan = await post_plan(self.host, self.port,
                                                   self._body(req.dag))
        except (OSError, ValueError, asyncio.IncompleteReadError) as e:
            req.error = repr(e)
        req.done = self.clock()
        if req.status != 200:
            req.error = req.error or f"HTTP {req.status}: {req.plan}"
            req.plan = None

    async def run(self, t0: float, seconds: float,
                  drain_s: float = 60.0) -> None:
        """Offer the window [t0, t0 + seconds), then wait up to ``drain_s``
        past its close for the answers still due."""
        if self.traffic["loop"] == "open":
            tasks = []
            for off in due_offsets(self.traffic, seconds):
                req = self._next(t0 + off)
                delay = req.due - self.clock()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(asyncio.create_task(self._send(req)))
        elif self.traffic["loop"] == "closed":
            end = t0 + seconds
            stagger = float(self.traffic.get("stagger_s", 0.0))

            async def caller(c: int) -> None:
                await asyncio.sleep(max(0.0, t0 + stagger
                                        * (c // self.max_batch)
                                        - self.clock()))
                while self.clock() < end:
                    await self._send(self._next(self.clock()))

            tasks = [asyncio.create_task(caller(c))
                     for c in range(int(self.traffic["outstanding"]))]
        else:
            raise ValueError(f"unknown loop {self.traffic['loop']!r}")
        left = t0 + seconds + drain_s - self.clock()
        done, pending = await asyncio.wait(tasks, timeout=max(left, 0.0))
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
        for t in done:
            t.result()
        for req in self.requests:
            if req.done is None:
                req.error = req.error or "no answer within the drain"

    def lateness(self) -> List[float]:
        """How late each request was sent against its due time (open loop)."""
        return [r.sent - r.due for r in self.requests if r.sent is not None]
