#!/usr/bin/env python3
"""Knee sweep: the highest open-loop rate a configuration sustains.

    python3 perfbench/sweep.py --config paper_m5_airflow --seed 5 \
        --seconds 40 --rates 1.0 1.2 1.4 1.6

One process sets up the configuration's served planner once (every
bucket warmed), then offers Poisson arrivals (the benchmark's own
generator, arrivals ordered by ``--seed`` plus the rate's index) at each
rate in turn for ``--seconds``, draining between rates.
Per rate it prints one JSON line: requests offered, answered inside the
window, still unanswered when the window closed, plans completed per
second inside the window, the median and 90th-percentile latency, and the
mean latency of the window's last third over that of its first third. A
rate is sustained while the window's completions keep up with its
arrivals and the latency does not grow across the window.
"""
import time

T_START = time.monotonic()

import argparse
import asyncio
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(CHECKOUT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from harness import bench, manifest, stats
    from harness.traffic import Generator
    man = manifest.load_manifest()
    conf = {c["name"]: c for c in man["configs"]}[args.config]
    with open(os.path.join(CHECKOUT, conf["file"])) as f:
        config = json.load(f)

    import jax
    if jax.default_backend() != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))
    from repro.flow.daemon import PlannerHTTPServer, dag_from_json
    max_batch = int(config["daemon"]["max_batch"])
    pop = manifest.population(config["population"]["kind"])
    dags = pop.stream(config["population"], config["cluster"],
                      bench._rng(args.seed, 1))
    service = bench._build_service(config, None)
    for tpl in pop.templates(config["population"], config["cluster"]):
        service.warmup(dag_from_json(tpl),
                       buckets=bench._warm_buckets({"warm": "all"}, max_batch))
    print(f"setup {time.monotonic() - T_START:.1f} s", flush=True)

    async def sweep():
        async with service:
            http = PlannerHTTPServer(service)
            host, port = await http.start()
            try:
                for i, rate in enumerate(args.rates):
                    traffic = {"loop": "open", "arrivals": "poisson",
                               "rate_per_s": rate,
                               "arrival_seed": args.seed + i}
                    gen = Generator(traffic, dags, host, port, max_batch)
                    t0 = time.monotonic()
                    t1 = t0 + args.seconds
                    await gen.run(t0, args.seconds)
                    reqs = gen.requests
                    lat = [r.latency for r in reqs]
                    third = max(len(reqs) // 3, 1)
                    first = [r.latency for r in reqs[:third]]
                    last = [r.latency for r in reqs[-third:]]
                    done = [r for r in reqs if r.ok and r.done <= t1]
                    print(json.dumps({
                        "rate_per_s": rate, "offered": len(reqs),
                        "answered_in_window": len(done),
                        "open_at_close": len(reqs) - len(done),
                        "failed": sum(not r.ok for r in reqs),
                        "completed_per_s": len(done) / args.seconds,
                        "latency_p50_s": stats.percentile(lat, 50),
                        "latency_p90_s": stats.percentile(lat, 90),
                        "latency_growth": (sum(last) / len(last))
                        / (sum(first) / len(first)),
                    }), flush=True)
            finally:
                await http.stop()

    asyncio.run(sweep())
    return 0


if __name__ == "__main__":
    sys.exit(main())
