"""Batched serving example: decode with an explicit KV/state cache across
three architecture families (dense GQA, RWKV6 state-based, Mamba2 hybrid).

  PYTHONPATH=src python examples/serve_batch.py
"""

from repro.launch.serve_model import serve


def main():
    for arch in ("smollm-360m", "rwkv6-3b", "zamba2-2.7b"):
        serve(arch=arch, smoke=True, batch=4, prompt_len=12, gen_tokens=20,
              temperature=0.8)


if __name__ == "__main__":
    main()
