"""Batched serving example: continuous batching over prefill + decode.

Loads a reduced-config architecture, enqueues more requests than the
batch size, and generates greedily -- slots are refilled as sequences
finish (the static-bucket continuous-batching discipline the decode_32k /
long_500k dry-run cells lower at production scale).

Run:  PYTHONPATH=src python examples/serve_lm.py --arch qwen2-1.5b
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models.lm import LM
from repro.runtime import enable_compile_cache
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch=args.batch, max_len=96)

    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(
                        1, cfg.vocab, int(rng.integers(3, 12)),
                        dtype=np.int64).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    results = eng.generate(reqs)
    dt = time.time() - t0
    n_tok = sum(len(v) for v in results.values())
    for uid in sorted(results):
        print(f"req {uid:2d} ({len(reqs[uid].prompt)} prompt toks) "
              f"-> {results[uid]}")
    print(f"\n{len(reqs)} requests, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok / dt:.1f} tok/s) with batch={args.batch} "
          f"continuous batching")
    assert len(results) == args.requests


if __name__ == "__main__":
    main()
