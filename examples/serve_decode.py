"""Serving example: batched generation + the paged-KV indirect stream kernel.

Part 1 serves a small dense model through the dense baseline loop (prefill
+ greedy decode with the sequence-sharded contiguous cache — what the
dry-run's decode cells lower).

Part 2 demonstrates the paged cache directly: scattered physical pages, a
page table as the AXI-Pack indirect stream descriptor, and the Pallas
``paged_decode_attention`` kernel consuming it (validated vs the oracle),
including the int8-packed variant (narrower elements → half the HBM
traffic, the paper's §III-E element-size argument).

Part 3 runs the continuous-batching scheduler: requests of different lengths
enter a tight page pool, prefill chunks interleave with batched decode
steps, and one request is evicted and replayed bit-for-bit; the scheduler's
counters say how many steps, tokens and evictions that took.

Part 4 re-runs the scheduler with ``kv_dtype='int8'``: the pools hold int8
codes plus fp32 scale sidebands, K/V rows are quantized on write, and both
attention kernels dequantize page-by-page (pool bytes ÷4 vs fp32).

Part 5 serves a *recurrent* model (RWKV6) through the very same scheduler:
fixed-size state slots instead of growing page chains, same
admission/eviction/replay machinery — the family protocol in action.

Run: PYTHONPATH=src python examples/serve_decode.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.kernels import ops, ref
from repro.launch.serve import dense_generate
from repro.models import lm
from repro.parallel.sharding import make_rules
from repro.runtime.device import use_compile_cache
from repro.serve import (
    PagedKVCache, PagedLM, RecurrentLM, Request, Scheduler,
    recurrent_reference_generate, static_batch_generate,
)

use_compile_cache()
rng = np.random.default_rng(0)

# --- Part 1: dense baseline loop ---------------------------------------------
cfg = smoke_config("yi-6b")
rules = make_rules(with_pod=False, batch_axes=None)
params = lm.init_model(cfg, jax.random.PRNGKey(0))
prompts = jnp.asarray(rng.integers(0, cfg.vocab, (4, 12)), jnp.int32)
out = dense_generate(cfg, params, rules, prompts, n_new=16, max_len=64)
print("dense baseline generated:", out.shape, "first row:", out[0][:8].tolist())

# --- Part 2: paged KV + indirect-stream kernel -------------------------------
B, H, KVH, D, page, npages = 4, 8, 2, 32, 16, 4
pool = 32
cache = PagedKVCache.create(smoke_config("yi-6b"), batch=B, max_len=page * npages,
                            page=page)
print(f"paged pool: {pool} pages × {page} tokens (free: {len(cache.free)})")

kp = jnp.asarray(rng.normal(size=(pool, page, KVH, D)), jnp.float32)
vp = jnp.asarray(rng.normal(size=(pool, page, KVH, D)), jnp.float32)
table = jnp.asarray(rng.permutation(pool)[: B * npages].reshape(B, npages),
                    jnp.int32)
lengths = jnp.asarray(rng.integers(1, page * npages, B), jnp.int32)
q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)

o_kernel = ops.paged_decode_attention(q, kp, vp, table, lengths)        # Pallas
o_oracle = ops.paged_decode_attention(q, kp, vp, table, lengths, impl="ref")
err = float(jnp.abs(o_kernel - o_oracle).max())
print(f"paged_decode kernel vs oracle: max err {err:.2e}")

# int8-packed pages: half the bytes per KV element on the stream
kq, ks = ref.int8_quantize(kp, axis=-1)
vq, vs = ref.int8_quantize(vp, axis=-1)
o_int8 = ops.paged_decode_attention(q, kq, vq, table, lengths,
                                    k_scale=ks[..., 0], v_scale=vs[..., 0])
q_err = float(jnp.abs(o_int8 - o_oracle).max())
bytes_bf16 = kp.size * 2 * 2
bytes_int8 = kp.size * 2 * 1 + ks.size * 4 * 2
print(f"int8-packed cache: err {q_err:.3f}, stream bytes "
      f"{bytes_bf16/2**20:.1f} MiB → {bytes_int8/2**20:.1f} MiB "
      f"({bytes_bf16/bytes_int8:.2f}x reduction)")

# --- Part 3: continuous-batching scheduler -----------------------------------
cfg3 = smoke_config("yi-6b")
model = PagedLM(cfg3, jax.random.PRNGKey(0), impl="ref")
prompts = [rng.integers(0, cfg3.vocab, n).astype(np.int32) for n in (8, 7, 12)]
max_new = 8

# Static reference: every request resident from step 0 in an ample pool.
want = static_batch_generate(
    model, PagedKVCache.create(cfg3, batch=3, max_len=32, page=4),
    prompts, max_new, chunk=4,
)

# Scheduled: a 9-page pool can't hold all three sequences at their peak, so
# admission staggers and the youngest resident gets evicted and replayed.
cache3 = PagedKVCache.create(cfg3, batch=3, max_len=32, page=4, pool_pages=9)
sched = Scheduler(model, cache3, chunk=4)
for i, p in enumerate(prompts):
    sched.submit(Request(
        rid=i, prompt=p, max_new=max_new,
        on_token=lambda r, t: print(f"  stream rid={r.rid} token={t}"),
    ))
out = sched.run()
st = sched.stats
match = all(out[i] == want[i] for i in out)
print(f"scheduler: {st.tokens} tokens in {st.prefill_steps} prefill and "
      f"{st.decode_steps} decode steps, {st.n_evictions} eviction(s); "
      f"matches static batch: {match}")
assert match, "scheduled decode diverged from the static batch"

# --- Part 4: int8 page pools under the scheduler -----------------------------
model8 = PagedLM(cfg3, jax.random.PRNGKey(0), impl="ref", kv_dtype="int8")
cache8 = PagedKVCache.create(cfg3, batch=3, max_len=32, page=4, pool_pages=9,
                             kv_dtype="int8")
sched8 = Scheduler(model8, cache8, chunk=4)
for i, p in enumerate(prompts):
    sched8.submit(Request(rid=i, prompt=p, max_new=max_new))
out8 = sched8.run()
st8 = sched8.stats
cache_fp = PagedKVCache.create(cfg3, batch=3, max_len=32, page=4, pool_pages=9)
print(f"int8 scheduler: {st8.tokens} tokens, pool "
      f"{cache_fp.pool_bytes/2**10:.0f} KiB fp32 → "
      f"{sched8.cache.pool_bytes/2**10:.0f} KiB int8 "
      f"({cache_fp.pool_bytes / sched8.cache.pool_bytes:.2f}x smaller)")
# Greedy decode is robust to the quantization noise on this workload: the
# token streams match the full-precision run exactly.
print("int8 tokens match fp32 run:", out8 == out)
assert out8 == out, "int8 greedy decode diverged from the fp32 run"

# --- Part 5: a recurrent family through the same scheduler -------------------
cfgr = smoke_config("rwkv6-3b")
rlm = RecurrentLM(cfgr, jax.random.PRNGKey(0))
rprompts = [rng.integers(0, cfgr.vocab, n).astype(np.int32) for n in (8, 7, 12)]
# Direct forward, each prompt fed in the scheduler's chunks — the ground
# truth.
want_r = recurrent_reference_generate(rlm, rlm.init_pool(3), rprompts, max_new,
                                      chunk=4)

# Same scheduler class, zero paged-KV anything: one fixed-size state slot
# per resident.
sched_r = Scheduler(rlm.bind(rlm.init_pool(3)), chunk=4)
for i, p in enumerate(rprompts):
    sched_r.submit(Request(rid=i, prompt=p, max_new=max_new))
out_r = sched_r.run()
st_r = sched_r.stats
match_r = all(out_r[i] == want_r[i] for i in out_r)
print(f"recurrent scheduler: {st_r.tokens} tokens in {st_r.prefill_steps} "
      f"prefill and {st_r.decode_steps} decode steps; matches direct "
      f"forward: {match_r}")
assert match_r, "recurrent scheduled decode diverged from direct forward"
