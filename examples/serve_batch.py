"""Batched serving example: prefill a batch of prompts, decode with a KV
cache, report throughput.

  PYTHONPATH=src python examples/serve_batch.py --arch gemma3-12b
  PYTHONPATH=src python examples/serve_batch.py --arch zamba2-2.7b --gen 64
"""

import argparse
import sys

sys.path.insert(0, "src")

from repro.launch.serve import serve


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-12b",
                    help="any assigned architecture (smoke config)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    out = serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
                gen=args.gen, smoke=True)
    decoded = args.batch * (args.gen - 1)
    print(f"generated token matrix: {out['tokens'].shape}; "
          f"decode throughput {decoded / out['decode_s']:.1f} tok/s "
          f"(CPU smoke config — the same code path drives a pod)")


if __name__ == "__main__":
    main()
