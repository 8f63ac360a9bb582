"""Serving driver: batched requests through prefill + decode.

This is one *instance* in the paper's co-location model — see
examples/colocated_serving.py for the full scheduler-driven deployment.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.jax_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(args.seed))
    engine = ServeEngine(api, params, batch_size=args.batch, seq_len=args.seq)

    rng = np.random.default_rng(args.seed)
    reqs = [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab, rng.integers(8, args.seq),
                                    dtype=np.int32),
                max_new_tokens=args.new_tokens)
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    engine.run(reqs)
    dt = time.perf_counter() - t0
    toks = engine.stats["tokens"]
    print(f"served {len(reqs)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s)")
    dec = engine.stats["decode_s"]
    if dec:
        print(f"decode p50 {1e3 * np.percentile(dec, 50):.1f}ms "
              f"p90 {1e3 * np.percentile(dec, 90):.1f}ms")
    for r in reqs[:2]:
        print(f"req {r.rid}: {r.output[:8]}...")


if __name__ == "__main__":
    main()
