"""Batched serving demo: bucketed waves over the universal decode engine.

Builds a small model, submits a mixed bag of requests with different prompt
lengths, and serves them in length-bucketed waves (prefill + greedy decode).
Works identically for KV-cache models and recurrent-state models — swap
--arch rwkv6-3b to serve the attention-free architecture with O(1) state.

Run:  PYTHONPATH=src python examples/serve_lm.py [--arch rwkv6-3b]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import BucketServer, Request

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="olmo-1b")
ap.add_argument("--requests", type=int, default=10)
ap.add_argument("--max-new", type=int, default=12)
args = ap.parse_args()

cfg = get_config(args.arch).reduced()
model = build_model(cfg)
if model.decode_step is None:
    raise SystemExit(f"{args.arch} is encoder-only; it has no decode step")
params = model.init_params(jax.random.PRNGKey(0))

rng = np.random.default_rng(0)
server = BucketServer(model, params, max_batch=4)
for i in range(args.requests):
    plen = int(rng.choice([8, 8, 8, 16, 16, 24]))  # mixed prompt lengths
    server.submit(Request(
        uid=i,
        prompt=rng.integers(0, cfg.vocab, size=plen).astype(np.int32),
        max_new=args.max_new,
    ))

t0 = time.time()
done = server.drain()
dt = time.time() - t0
total_tokens = sum(len(c.tokens) for c in done)
print(f"arch={args.arch}: served {len(done)} requests, "
      f"{total_tokens} tokens in {dt:.2f}s ({total_tokens/dt:.1f} tok/s)")
for c in sorted(done, key=lambda c: c.uid)[:5]:
    print(f"  req {c.uid}: {c.tokens.tolist()}")
