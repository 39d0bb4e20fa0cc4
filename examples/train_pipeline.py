"""End-to-end driver: AGORA plans an ML pipeline DAG (data prep -> train ->
eval -> package), the flow executor runs it for real — the training task is
an actual JAX training run (reduced model on CPU; pass --large for a
~100M-parameter smollm-360m at full width).

  PYTHONPATH=src python examples/train_pipeline.py [--steps 200] [--large]
"""
import argparse
import os
import tempfile

import numpy as np

from repro.cluster.catalog import tpu_cluster
from repro.core.agora import Agora
from repro.core.dag import DAG, Task, TaskOption
from repro.core.objectives import Goal
from repro.flow.executor import FlowConfig, FlowRunner
from repro.launch.serve_model import serve
from repro.launch.train import train


def pipeline_dag(cluster, steps: int):
    """4-task ML pipeline. Options follow a USL-ish scaling over TPU slices;
    the planner picks slice sizes + schedule (on CPU, runtimes are nominal)."""
    def opts(base_s, scale=0.8):
        out = []
        for m, t in enumerate(cluster.types):
            n = t.vcpus  # chips per slice
            d = base_s * (1.0 + scale * (n / 4 - 1)) / (n / 4)  # diminishing
            demands = [0.0] * cluster.num_resources
            demands[m] = 1.0
            out.append(TaskOption(f"1 x {t.name}", d, tuple(demands),
                                  d * t.price_per_sec))
        return out

    tasks = [
        Task("data-prep", opts(120.0)),
        Task("train-lm", opts(20.0 * steps)),
        Task("eval-lm", opts(90.0)),
        Task("package", opts(30.0)),
    ]
    return DAG("ml-pipeline", tasks, edges=[(0, 1), (1, 2), (2, 3)])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--large", action="store_true",
                    help="train full-width smollm-360m (slow on CPU)")
    args = ap.parse_args()

    cluster = tpu_cluster()
    dag = pipeline_dag(cluster, args.steps)
    agora = Agora(cluster, goal=Goal.balanced(), solver="anneal")
    plan = agora.plan([dag])
    print("AGORA plan:")
    for t, lbl in zip(plan.problem.tasks, plan.config_labels()):
        j = plan.problem.tasks.index(t)
        print(f"  {t.name:<10} {lbl:<14} start={plan.solution.start[j]:7.0f}s")
    print(f"  predicted makespan {plan.makespan:.0f}s, cost ${plan.cost:.2f}\n")

    ckpt_dir = tempfile.mkdtemp(prefix="repro_ckpt_")
    state = {}

    def do_data_prep():
        from repro.data.pipeline import DataConfig, TokenPipeline
        cfg = DataConfig(vocab_size=256, seq_len=128, global_batch=8)
        pipe = TokenPipeline(cfg)
        b = pipe.batch_at(0)
        print(f"  [data-prep] pipeline ready, batch shape {b['tokens'].shape}")

    def do_train():
        out = train(arch="smollm-360m", smoke=not args.large,
                    steps=args.steps, batch=8, seq=128, lr=2e-3,
                    ckpt_dir=ckpt_dir, ckpt_every=max(args.steps // 4, 10),
                    log_every=max(args.steps // 5, 10))
        state["train"] = out
        first = np.mean(out["losses"][:10])
        last = np.mean(out["losses"][-10:])
        print(f"  [train-lm] loss {first:.3f} -> {last:.3f} "
              f"({out['steps_run']} steps)")
        assert last < first, "training did not reduce loss"

    def do_eval():
        out = serve(arch="smollm-360m", smoke=not args.large, batch=2,
                    prompt_len=8, gen_tokens=8,
                    params=state["train"]["params"], quiet=True)
        print(f"  [eval-lm] generated {out['tokens'].shape} tokens "
              f"in {out['seconds']:.1f}s")

    def do_package():
        steps = sorted(os.listdir(ckpt_dir))
        print(f"  [package] checkpoints: {steps}")

    fns = {0: do_data_prep, 1: do_train, 2: do_eval, 3: do_package}
    runner = FlowRunner(plan, FlowConfig(mode="real"), fns=fns)
    result = runner.run()
    print(f"\npipeline complete: {len(result.task_finish)} tasks, "
          f"retries={result.retries}")


if __name__ == "__main__":
    main()
