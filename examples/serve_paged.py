"""Serving with a PULSE-paged KV cache: the page-table walk IS a pointer
traversal (DESIGN.md S3).

Decodes from a small GQA model with per-sequence page chains living in a
PULSE arena; every step walks the chains with the batched iterator executor
and runs decode attention over the gathered pages (validated against the
kernel reference).  Also serves a request batch via continuous batching.

Run: PYTHONPATH=src python examples/serve_paged.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_reduced_config
from repro.kernels.paged_attention.ops import paged_attention
from repro.models.model_zoo import build_model
from repro.serving.batching import ContinuousBatcher, Request
from repro.serving.kv_cache import PagedKVCache
from repro.compile_cache import use_compile_cache

use_compile_cache()

rng = np.random.default_rng(0)
cfg = get_reduced_config("qwen3_4b")
print(f"model: reduced qwen3-4b ({cfg.n_layers}L d{cfg.d_model} GQA "
      f"{cfg.n_heads}/{cfg.n_kv_heads})")

# --- 1) the paged cache: chains in a PULSE arena ---------------------------
B, page_size, n_pages = 4, 8, 64
cache = PagedKVCache(cfg, n_pages=n_pages, page_size=page_size, max_batch=B)
lens = [27, 9, 40, 16]
for b, ln in enumerate(lens):
    cache.ensure_capacity(b, ln)
    cache.lengths[b] = ln
pt, lengths = cache.walk_page_tables(max_pages=8)
print(f"page tables (PULSE chain walk): lengths={np.asarray(lengths)}")
print(np.asarray(pt))

# fill pages with random KV and check paged attention against dense math
Hk, hd = cfg.n_kv_heads, cfg.hd
k_pages = jnp.asarray(rng.standard_normal(cache.k_pages.shape[1:]), jnp.float32)
v_pages = jnp.asarray(rng.standard_normal(cache.v_pages.shape[1:]), jnp.float32)
q = jnp.asarray(rng.standard_normal((B, cfg.n_heads, hd)), jnp.float32)
o = paged_attention(q, k_pages, v_pages, pt, lengths, interpret=True, use_pallas=True)
o_ref = paged_attention(q, k_pages, v_pages, pt, lengths, use_pallas=False)
err = float(jnp.abs(o - o_ref).max())
print(f"paged decode attention (pulse_chase + flash-decode kernel): "
      f"max |kernel - ref| = {err:.2e}")
assert err < 1e-4

# --- 2) continuous batching over the model zoo -----------------------------
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
reqs = [
    Request(req_id=i, prompt=rng.integers(2, cfg.vocab, 6).astype(np.int32),
            max_new_tokens=12)
    for i in range(6)
]
b = ContinuousBatcher(model, max_batch=3, max_len=32)
b.model_params = params
m = b.serve(reqs)
done = sum(1 for r in reqs if r.finished_step >= 0)
print(f"continuous batching: {done}/{len(reqs)} requests, {m.tokens_out} tokens "
      f"in {m.steps} decode steps ({m.tokens_per_s:.1f} tok/s CPU)")
