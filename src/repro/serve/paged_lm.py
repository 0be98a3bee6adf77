"""Paged transformer engine: an attention-only LM over the page pool.

Split out of the old ``serve/engine.py`` next to ``serve/kv.py`` (the pool)
and ``serve/family.py`` (the scheduler protocol).  This module owns the
jitted prefill/decode programs, the :class:`PagedLM` wrapper that keeps the
cache's host shadows in step, and :class:`PagedFamily` — the
:class:`repro.serve.family.ServableFamily` implementation the scheduler
drives (resource units = pages).

The paged path is built as a *device-resident fast path*: the page pools are
donated into every jitted call (``donate_argnums``) so they update in place
instead of being copied per step — each layer addresses its pages inside the
one donated pool (:func:`_layer_view`), never a slice of it — greedy
sampling happens on device, and
``decode_steps`` fuses ``n`` decode iterations into one ``lax.scan`` launch
that feeds its own samples back — the host only sees tokens when the
scheduler reaches a scheduling boundary (admission, page growth,
retirement).  Host-side shadow state (``lengths_host``/``page_table_host``)
lets all bookkeeping run without a single device→host sync on the hot
path.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.kernels.paged_decode import pages_per_block
from repro.runtime.tracing import span
from .drafter import Drafter, NGramDrafter
from .family import ServableFamily
from .kv import PagedKVCache, _donation_noop_ok

__all__ = ["PagedFamily", "PagedLM", "static_batch_generate"]


def _block_fill(lengths, active, page: int, ppb: int) -> float:
    """Share of the decode kernel's walked page slots that hold live pages:
    the active rows' pages over ``ppb`` times their blocks, at ``lengths``
    (the first step of a launch); 0 with no host shadow or no live page."""
    if lengths is None:
        return 0.0
    used = -(-np.asarray(lengths)[np.asarray(active, bool)] // page)
    slots = ppb * (-(-used // ppb)).sum()
    return float(used.sum() / slots) if slots else 0.0


def _layer_view(*pools):
    """Each ``(L, P, ...)`` pool (or ``None``) as one ``(L·P, ...)`` array.

    Merging the two leading dims is a bitcast, so the view is the donated
    buffer itself: a program addresses layer ``l``'s page ``j`` as row
    ``l·P + j`` (its page table plus ``l·P``) and every layer's write
    aliases the one pool in place.  Slicing ``pool[l]`` instead would hand
    the aliasing write kernels an operand that does not own its buffer, so
    XLA would copy each layer out and stack the layers back, a whole-pool
    copy per pool per program.
    """
    return [None if a is None else a.reshape((-1,) + a.shape[2:])
            for a in pools]


def _pool_view(shape, k_pages, v_pages, k_scale, v_scale):
    """The :func:`_layer_view` pools back in the cache's ``(L, P, ...)``
    ``shape`` (the scale pools without its last dim)."""
    return (k_pages.reshape(shape), v_pages.reshape(shape),
            None if k_scale is None else k_scale.reshape(shape[:-1]),
            None if v_scale is None else v_scale.reshape(shape[:-1]))


def _paged_lm_decode_step(params, tokens, k_pages, v_pages, k_scale, v_scale,
                          page_table, lengths, active, *, h, kvh, hd, impl):
    """One batched decode step against the paged pool.

    tokens (B,) int32; active (B,) bool — inactive slots write nothing, keep
    length 0 and produce zero attention.  Every array op is row-wise per
    sequence, so slot placement / batch composition never changes a
    sequence's bits.

    ``k_scale``/``v_scale`` are the (L, P, page, KVH) fp32 scale pools of an
    int8 KV pool, or ``None`` in full-precision mode: when given, the append
    quantizes on write (codes + scales through the same indirect burst) and
    attention dequantizes page-by-page in VMEM.

    Every layer writes and reads its own pages inside the one pool value
    (:func:`_layer_view`), so with the pools donated at the jit boundary
    XLA aliases each layer's write into the input buffers — an in-place
    update of the resident pool, with no layer sliced out or stacked back.
    """
    n_layers, n_pool = k_pages.shape[:2]
    b = tokens.shape[0]
    quantized = k_scale is not None
    x = jnp.take(params["embed"], tokens, axis=0)          # (B, d)
    new_len = lengths + active.astype(lengths.dtype)
    kp, vp, ks, vs = _layer_view(k_pages, v_pages, k_scale, v_scale)
    for l in range(n_layers):
        q = (x @ params["wq"][l]).reshape(b, h, hd)
        kn = (x @ params["wk"][l]).reshape(b, kvh, hd)
        vn = (x @ params["wv"][l]).reshape(b, kvh, hd)
        table = page_table + l * n_pool
        scales = dict(k_scale=ks, v_scale=vs) if quantized else {}
        out = kops.paged_kv_append(
            kp, vp, kn, vn, table, lengths, active, impl=impl, **scales,
        )
        kp, vp = out[0], out[1]
        if quantized:
            ks, vs = out[3], out[4]
        attn = kops.paged_decode_attention(
            q, kp, vp, table, new_len, k_scale=ks, v_scale=vs, impl=impl
        )
        x = x + attn.reshape(b, h * hd) @ params["wo"][l]
    logits = x @ params["embed"].T                          # (B, vocab)
    return (logits, *_pool_view(k_pages.shape, kp, vp, ks, vs), new_len)


def _paged_lm_decode_steps(params, tokens, k_pages, v_pages, k_scale,
                           v_scale, page_table, lengths, active, *, n, vocab,
                           h, kvh, hd, impl):
    """``n`` fused decode steps with on-device greedy sampling.

    One ``lax.scan`` launch: each step runs the single-step core, argmaxes
    its own logits on device, and feeds the sample back as the next input —
    no logits or lengths ever cross to the host.  The scale pools (int8
    mode) ride the scan carry next to the K/V pools.  Returns the (n, B)
    token matrix, the final feed token (``toks[-1]``, returned from inside
    the graph so chained launches never slice on the host), and the updated
    pools/lengths; bitwise identical to ``n`` sequential
    :func:`_paged_lm_decode_step` calls with host-side argmax.
    """

    def body(carry, _):
        toks, kp, vp, ks, vs, lens = carry
        logits, kp, vp, ks, vs, lens = _paged_lm_decode_step(
            params, toks, kp, vp, ks, vs, page_table, lens, active,
            h=h, kvh=kvh, hd=hd, impl=impl,
        )
        nxt = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return (nxt, kp, vp, ks, vs, lens), nxt

    (last, k_pages, v_pages, k_scale, v_scale, lengths), toks = jax.lax.scan(
        body, (tokens, k_pages, v_pages, k_scale, v_scale, lengths), None,
        length=n,
    )
    return toks, last, k_pages, v_pages, k_scale, v_scale, lengths


def _paged_lm_prefill_batch(params, tokens, counts, seqs, starts, k_pages,
                            v_pages, k_scale, v_scale, page_table, lengths,
                            *, h, kvh, hd, page, ctx_pages, impl):
    """Advance every pending sequence by one prompt chunk, in one call.

    tokens (R, C) int32 (row r zero-padded past ``counts[r]``); ``seqs`` maps
    rows to batch slots and ``starts`` gives the absolute position of each
    row's tokens[0].  Rows with ``counts[r] == 0`` are padding and touch
    nothing.

    KV rows are scattered through the chunk-bounded indirect write
    (:func:`repro.kernels.ops.paged_kv_write_chunk` — R·W pages of traffic,
    never the whole pool), and each layer's attention runs through
    :func:`repro.kernels.ops.paged_prefill_attention` over only the leading
    ``ctx_pages`` table entries per sequence (the pages that can hold
    context for this chunk), never the full table row.  Under
    ``impl='pallas'`` the context pages stream HBM→VMEM one at a time with
    an online softmax (no gathered context or dense score tensor); under
    ``impl='ref'`` the dense-einsum oracle runs, masked with a finite
    constant so ``counts == 0`` padding rows can never produce NaN softmax
    outputs that poison the donated pools.  ``k_scale``/``v_scale`` (int8
    mode, or ``None``) make the chunk write quantize-on-write and the
    attention dequantize per context page.  Returns the last *real* token's
    logits per row plus the updated pools.
    """
    n_layers, n_pool = k_pages.shape[:2]
    r, c = tokens.shape
    quantized = k_scale is not None
    x = jnp.take(params["embed"], tokens, axis=0)          # (R, C, d)
    rows = jnp.take(page_table, seqs, axis=0)              # (R, n_pages)
    kp, vp, ks, vs = _layer_view(k_pages, v_pages, k_scale, v_scale)
    for l in range(n_layers):
        kn = (x @ params["wk"][l]).reshape(r, c, kvh, hd)
        vn = (x @ params["wv"][l]).reshape(r, c, kvh, hd)
        rows_l = rows + l * n_pool
        scales = dict(k_scale=ks, v_scale=vs) if quantized else {}
        out = kops.paged_kv_write_chunk(
            kp, vp, kn, vn, rows_l, starts, counts, impl=impl, **scales,
        )
        kp, vp = out[0], out[1]
        if quantized:
            ks, vs = out[2], out[3]
        q = (x @ params["wq"][l]).reshape(r, c, h, hd)
        attn = kops.paged_prefill_attention(
            q, kp, vp, rows_l[:, :ctx_pages], starts, counts, k_scale=ks,
            v_scale=vs, impl=impl,
        )
        x = x + attn.astype(x.dtype).reshape(r, c, h * hd) @ params["wo"][l]
    last = jnp.take_along_axis(
        x, jnp.clip(counts - 1, 0, c - 1)[:, None, None].astype(jnp.int32),
        axis=1,
    )[:, 0]                                                # (R, d)
    # Advance each real row's slot length in-graph (padding rows dropped).
    b = lengths.shape[0]
    new_len = lengths.at[jnp.where(counts > 0, seqs, b)].set(
        (starts + counts).astype(lengths.dtype), mode="drop"
    )
    return (last @ params["embed"].T,
            *_pool_view(k_pages.shape, kp, vp, ks, vs), new_len)


def _paged_lm_verify_step(params, q_tokens, k_pages, v_pages, k_scale,
                          v_scale, page_table, lengths, counts, *, h, kvh,
                          hd, ctx_pages, impl):
    """Score K speculative tokens per sequence in one multi-query pass.

    q_tokens (B, K) int32 — the feed token at column 0, draft tokens after
    it; row ``b``'s token ``i`` sits at absolute position
    ``lengths[b] + i``.  ``counts`` (B,) bounds the valid tokens per row
    (0..K; 0 = inactive row, touches nothing).

    Structurally one prefill-chunk pass with ``starts = lengths``: each
    layer scatters the chunk's K/V through the chunk-bounded indirect
    write, then :func:`repro.kernels.ops.paged_verify` scores all K causal
    queries in **one** clamped page walk — the walk plain decode would
    repeat K times.  Returns per-position logits (B, K, vocab) plus the
    updated pools; lengths are *not* advanced here (the accept step owns
    that, since only accepted tokens survive).
    """
    n_layers, n_pool = k_pages.shape[:2]
    b, k = q_tokens.shape
    quantized = k_scale is not None
    x = jnp.take(params["embed"], q_tokens, axis=0)        # (B, K, d)
    kp, vp, ks, vs = _layer_view(k_pages, v_pages, k_scale, v_scale)
    for l in range(n_layers):
        kn = (x @ params["wk"][l]).reshape(b, k, kvh, hd)
        vn = (x @ params["wv"][l]).reshape(b, k, kvh, hd)
        table = page_table + l * n_pool
        scales = dict(k_scale=ks, v_scale=vs) if quantized else {}
        out = kops.paged_kv_write_chunk(
            kp, vp, kn, vn, table, lengths, counts, impl=impl, **scales,
        )
        kp, vp = out[0], out[1]
        if quantized:
            ks, vs = out[2], out[3]
        q = (x @ params["wq"][l]).reshape(b, k, h, hd)
        attn = kops.paged_verify(
            q, kp, vp, table[:, :ctx_pages], lengths, counts, k_scale=ks,
            v_scale=vs, impl=impl,
        )
        x = x + attn.astype(x.dtype).reshape(b, k, h * hd) @ params["wo"][l]
    logits = x @ params["embed"].T                          # (B, K, vocab)
    return (logits, *_pool_view(k_pages.shape, kp, vp, ks, vs))


def _paged_lm_verify_steps(params, feed, dstate, k_pages, v_pages, k_scale,
                           v_scale, page_table, lengths, active, caps, *,
                           drafter, n, spec_k, vocab, h, kvh, hd, ctx_pages,
                           impl):
    """``n`` fused draft→verify→accept iterations in one ``lax.scan``.

    The speculative hot loop, entirely on device: each iteration drafts
    ``spec_k - 1`` tokens from the drafter state, scores feed+drafts with
    :func:`_paged_lm_verify_step`, greedy-accepts the matched prefix plus
    the model's bonus token (:func:`repro.kernels.ops.speculative_accept`),
    advances lengths by the emitted count (the KV *rollback*: rejected
    appends past the first mismatch are simply left behind the new length,
    masked out of every later attention and overwritten by the next
    iteration's chunk write), and folds the outcome into the drafter
    state.  The host sees nothing until the caller syncs the stacked
    (n, B, K) token / (n, B) count outputs at the launch boundary.

    ``caps`` (B,) is each slot's mapped-token capacity: per iteration the
    scored count is clamped in-graph to ``min(spec_k, caps - lengths)``
    so speculation can never write past a slot's mapped pages —
    capacity-starved slots degrade towards fewer scored tokens (0 = the
    slot stalls until the scheduler grows it).

    Emitted tokens are the target model's argmax only — bitwise the plain
    greedy decode sequence regardless of drafts or drafter state (wrong
    drafts cost acceptance rate, never bits).
    """

    def body(carry, _):
        fd, ds, kp, vp, ks, vs, lens = carry
        drafts = drafter.draft(ds, fd, spec_k - 1)          # (B, K-1)
        q_tokens = jnp.concatenate(
            [fd[:, None], drafts.astype(jnp.int32)], axis=1
        )
        counts = jnp.where(
            active, jnp.clip(caps - lens, 0, spec_k), 0
        ).astype(jnp.int32)
        logits, kp, vp, ks, vs = _paged_lm_verify_step(
            params, q_tokens, kp, vp, ks, vs, page_table, lens, counts,
            h=h, kvh=kvh, hd=hd, ctx_pages=ctx_pages, impl=impl,
        )
        g = jnp.argmax(logits[..., :vocab], axis=-1).astype(jnp.int32)
        n_emit = kops.speculative_accept(drafts, g, counts)
        fd = jnp.where(
            n_emit > 0,
            jnp.take_along_axis(
                g, jnp.clip(n_emit - 1, 0, spec_k - 1)[:, None], axis=1
            )[:, 0],
            fd,
        )
        ds = drafter.update(ds, q_tokens, g, n_emit)
        lens = lens + n_emit.astype(lens.dtype)
        return (fd, ds, kp, vp, ks, vs, lens), (g, n_emit)

    carry = (feed, dstate, k_pages, v_pages, k_scale, v_scale, lengths)
    (feed, dstate, k_pages, v_pages, k_scale, v_scale, lengths), \
        (toks, counts) = jax.lax.scan(body, carry, None, length=n)
    return (toks, counts, feed, dstate, k_pages, v_pages, k_scale, v_scale,
            lengths)


def _jit(fn, static_argnames=(), donate_argnums=(), **bound):
    """``jax.jit`` of ``fn`` with ``bound`` keywords fixed, named after
    ``fn`` (``jit_paged_lm_decode_steps`` in a profile; a bare
    ``functools.partial`` would read ``jit__unknown``)."""
    f = functools.partial(fn, **bound)
    f.__name__ = fn.__name__.lstrip("_")
    return jax.jit(f, static_argnames=static_argnames,
                   donate_argnums=donate_argnums)


class PagedLM:
    """Attention-only LM serving straight out of a :class:`PagedKVCache`.

    Deliberately minimal (tied embeddings, no norms/MLP, greedy-friendly
    float32 math): every per-token computation is row-wise, so a sequence's
    outputs depend only on its own tokens and pages — the property the
    scheduler's static-batch equivalence guarantees rest on.  All heavy data
    movement runs through the packed stream ops: ``paged_kv_append`` /
    ``paged_kv_write_chunk`` (the indirect write converters) and
    ``paged_decode_attention`` (the indirect read / scalar-prefetch kernel).

    Every jitted entry point donates the page pools, and the wrappers keep
    the cache's host shadows (``lengths_host``) in step arithmetically, so
    calling code never needs to read device state back.

    ``kv_dtype='int8'`` serves from quantized page pools: K/V rows are
    quantized on write (per-(token, kv-head) scales into the donated scale
    pools) and both attention kernels dequantize page-by-page in VMEM — the
    serving analogue of packing narrower elements onto a fixed-width bus
    (packing factor ``bus/elem``: 8-bit elements quadruple the FP32 factor).
    The matching cache must be created with the same ``kv_dtype``.

    ``params`` serves given weights (e.g. another engine's, so a ``ref``
    and a ``pallas`` engine can share one copy) instead of drawing random
    ones from ``key``.
    """

    #: Max resident jitted prefill *and* verify programs (one shared LRU).
    #: Each distinct ``(page, ctx)`` prefill bucket or
    #: ``("verify", spec_k, page, ctx)`` verify bucket mints one program;
    #: ragged prompt-length traffic over many page sizes would otherwise
    #: grow the cache without bound.
    PREFILL_CACHE_CAP = 8

    def __init__(self, cfg: ArchConfig, key: jax.Array, impl: str = "pallas",
                 prefill_cache_cap: Optional[int] = None,
                 kv_dtype: Optional[str] = None, spec_k: int = 1,
                 drafter: Optional[Drafter] = None,
                 params: Optional[Dict[str, jax.Array]] = None):
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.cfg = cfg
        self.impl = impl
        self.spec_k = spec_k
        self.drafter = drafter if drafter is not None else NGramDrafter(
            cfg.vocab
        )
        self.kv_dtype = (
            PagedKVCache.KV_DTYPES[kv_dtype] if kv_dtype is not None
            else cfg.compute_dtype
        )
        h, kvh = cfg.heads_for_tp(1)
        self.h, self.kvh, self.hd = h, kvh, cfg.hd
        d, L = cfg.d_model, cfg.n_layers
        self.prefill_cache_cap = (
            self.PREFILL_CACHE_CAP if prefill_cache_cap is None
            else prefill_cache_cap
        )
        # LRU over (page, ctx_pages) buckets: refreshed on hit, evicted
        # oldest-first past the cap (a re-requested evicted bucket simply
        # re-jits — correctness never depends on residency).
        self._prefill_cache: "collections.OrderedDict[Tuple[int, int], Any]" \
            = collections.OrderedDict()
        if params is None:
            ks = jax.random.split(key, 5)
            init = lambda k, *s: (jax.random.normal(k, s, jnp.float32)
                                  / np.sqrt(s[-2]))
            params = {
                "embed": jax.random.normal(ks[0], (cfg.vocab, d),
                                           jnp.float32) * 0.02,
                "wq": init(ks[1], L, d, h * cfg.hd),
                "wk": init(ks[2], L, d, kvh * cfg.hd),
                "wv": init(ks[3], L, d, kvh * cfg.hd),
                "wo": init(ks[4], L, h * cfg.hd, d),
            }
        self.params = params

    def bind(self, cache: PagedKVCache) -> "PagedFamily":
        """Wrap this model + ``cache`` as the scheduler-facing family."""
        return PagedFamily(self, cache)

    @functools.cached_property
    def _decode(self):
        return _jit(_paged_lm_decode_step, donate_argnums=(2, 3, 4, 5),
                    h=self.h, kvh=self.kvh, hd=self.hd, impl=self.impl)

    @functools.cached_property
    def _decode_many(self):
        return _jit(_paged_lm_decode_steps, static_argnames=("n",),
                    donate_argnums=(2, 3, 4, 5), vocab=self.cfg.vocab,
                    h=self.h, kvh=self.kvh, hd=self.hd, impl=self.impl)

    def _prefill(self, page: int, ctx_pages: int):
        return _jit(_paged_lm_prefill_batch, donate_argnums=(5, 6, 7, 8),
                    h=self.h, kvh=self.kvh, hd=self.hd, page=page,
                    ctx_pages=ctx_pages, impl=self.impl)

    def _verify(self, spec_k: int, ctx_pages: int):
        return _jit(_paged_lm_verify_steps, static_argnames=("n",),
                    donate_argnums=(3, 4, 5, 6), drafter=self.drafter,
                    spec_k=spec_k, vocab=self.cfg.vocab, h=self.h,
                    kvh=self.kvh, hd=self.hd, ctx_pages=ctx_pages,
                    impl=self.impl)

    def _cached_program(self, key, make):
        """Shared LRU over jitted prefill *and* verify programs: refreshed
        on hit, evicted oldest-first past the cap (an evicted bucket
        transparently re-jits — correctness never depends on residency)."""
        fn = self._prefill_cache.get(key)
        if fn is None:
            fn = self._prefill_cache[key] = make()
            while len(self._prefill_cache) > self.prefill_cache_cap:
                self._prefill_cache.popitem(last=False)
        else:
            self._prefill_cache.move_to_end(key)
        return fn

    # -- decode --------------------------------------------------------------

    def _shift_lengths(self, cache: PagedKVCache, active, steps: int):
        if cache.lengths_host is None:
            return None
        return (cache.lengths_host
                + steps * np.asarray(active).astype(np.int32))

    def decode_step(self, tokens, cache: PagedKVCache, active):
        """One decode step; returns (logits, cache).  Pools are donated —
        the passed-in cache's device arrays must not be reused."""
        act_host = np.asarray(active)
        with _donation_noop_ok():
            logits, kp, vp, ks, vs, new_len = self._decode(
                self.params, jnp.asarray(tokens), cache.k_pages,
                cache.v_pages, cache.k_scale, cache.v_scale,
                cache.page_table, cache.lengths,
                jnp.asarray(active),
            )
        cache = dataclasses.replace(
            cache, k_pages=kp, v_pages=vp, k_scale=ks, v_scale=vs,
            lengths=new_len,
            lengths_host=self._shift_lengths(cache, act_host, 1),
        )
        return logits, cache

    def decode_steps(self, tokens, cache: PagedKVCache, active, n: int):
        """``n`` fused decode steps with device-side greedy sampling.

        Returns (tokens (n, B) — a *device* array, synced only when the
        caller reads it — and the updated cache).  Bitwise equivalent to
        ``n`` sequential ``decode_step`` + host argmax iterations.
        """
        act_host = np.asarray(active)
        with _donation_noop_ok():
            toks, _, kp, vp, ks, vs, new_len = self._decode_many(
                self.params, jnp.asarray(tokens), cache.k_pages,
                cache.v_pages, cache.k_scale, cache.v_scale,
                cache.page_table, cache.lengths,
                jnp.asarray(active), n=n,
            )
        cache = dataclasses.replace(
            cache, k_pages=kp, v_pages=vp, k_scale=ks, v_scale=vs,
            lengths=new_len,
            lengths_host=self._shift_lengths(cache, act_host, n),
        )
        return toks, cache

    def decode_upto(self, tokens, cache: PagedKVCache, active, n: int):
        """Fused decode of exactly ``n`` steps as a chain of pow2 scans.

        Power-of-two scan lengths keep the jit cache to O(log n) entries
        while the feed token, pools, and lengths stay on device between
        chunks; the (n, B) token matrix crosses to the host exactly once,
        here.  Returns (tokens (n, B) np.ndarray, cache).
        """
        act_host = np.asarray(active)
        act_dev = jnp.asarray(active)
        feed = jnp.asarray(tokens)
        kp, vp = cache.k_pages, cache.v_pages
        ks, vs = cache.k_scale, cache.v_scale
        lens = cache.lengths
        ppb = pages_per_block(cache.page_size, self.kvh, self.hd,
                              kp.dtype, cache.pages_per_seq, cache.quantized)
        parts = []
        rem = n
        done = 0
        with _donation_noop_ok():
            while rem:
                m = 1 << (rem.bit_length() - 1)
                with span("engine.launch", kind="decode", n=m,
                          ctx_pages=cache.pages_per_seq, ppb=ppb,
                          block_fill=_block_fill(
                              self._shift_lengths(cache, act_host, done + 1),
                              act_host, cache.page_size, ppb)):
                    toks, feed, kp, vp, ks, vs, lens = self._decode_many(
                        self.params, feed, kp, vp, ks, vs, cache.page_table,
                        lens, act_dev, n=m,
                    )
                parts.append(toks)
                rem -= m
                done += m
        with span("engine.sync", kind="decode"):
            out = np.concatenate([np.asarray(t) for t in parts], axis=0)
        cache = dataclasses.replace(
            cache, k_pages=kp, v_pages=vp, k_scale=ks, v_scale=vs,
            lengths=lens,
            lengths_host=self._shift_lengths(cache, act_host, n),
        )
        return out, cache

    # -- speculative verify --------------------------------------------------

    def verify_upto(self, tokens, cache: PagedKVCache, active, n: int,
                    dstate):
        """``n`` fused draft→verify→accept iterations as pow2 scan chains.

        tokens (B,) int32 feed tokens; ``dstate`` is the drafter state
        pytree (see :class:`repro.serve.drafter.Drafter`).  Like
        :meth:`decode_upto`, power-of-two scan lengths bound the jit cache
        to O(log n) compilations per ``("verify", spec_k, page, ctx)``
        bucket while feed/drafter-state/pools/lengths stay on device
        between chunks; the stacked outputs cross to the host exactly
        once, here.

        Returns ``(toks (n, B, K) np.ndarray, counts (n, B) np.ndarray,
        cache, dstate)`` — step ``s`` emitted ``counts[s, b]`` tokens for
        slot ``b``, namely ``toks[s, b, :counts[s, b]]``.  Unlike plain
        decode the per-step advance is data-dependent, so the host
        lengths shadow is reconciled from the synced counts (still one
        sync per launch).
        """
        k = self.spec_k
        b = cache.page_table.shape[0]
        page = cache.page_size
        lens_host = (cache.lengths_host if cache.lengths_host is not None
                     else np.asarray(cache.lengths))
        act_host = np.asarray(active).astype(bool)
        caps = np.array(
            [cache._mapped(s) * page for s in range(b)], np.int64
        )
        # Context bucket: the furthest any slot can reach this launch.
        hi = np.where(
            act_host, np.minimum(lens_host + n * k, caps), lens_host
        )
        need = int(max(1, -(-int(hi.max()) // page)))
        ctx = 1
        while ctx < need:
            ctx *= 2
        ctx = min(ctx, cache.pages_per_seq)
        fn = self._cached_program(
            ("verify", k, page, ctx), lambda: self._verify(k, ctx)
        )
        feed = jnp.asarray(tokens)
        act_dev = jnp.asarray(act_host)
        caps_dev = jnp.asarray(caps, jnp.int32)
        kp, vp = cache.k_pages, cache.v_pages
        ks, vs = cache.k_scale, cache.v_scale
        lens = cache.lengths
        tok_parts, cnt_parts = [], []
        rem = n
        with _donation_noop_ok():
            while rem:
                m = 1 << (rem.bit_length() - 1)
                with span("engine.launch", kind="verify", n=m, ctx_pages=ctx):
                    toks, counts, feed, dstate, kp, vp, ks, vs, lens = fn(
                        self.params, feed, dstate, kp, vp, ks, vs,
                        cache.page_table, lens, act_dev, caps_dev, n=m,
                    )
                tok_parts.append(toks)
                cnt_parts.append(counts)
                rem -= m
        with span("engine.sync", kind="verify"):
            toks_h = np.concatenate([np.asarray(t) for t in tok_parts],
                                    axis=0)
            counts_h = np.concatenate([np.asarray(c) for c in cnt_parts],
                                      axis=0)
        cache = dataclasses.replace(
            cache, k_pages=kp, v_pages=vp, k_scale=ks, v_scale=vs,
            lengths=lens,
            lengths_host=(lens_host + counts_h.sum(axis=0)).astype(
                lens_host.dtype
            ) if cache.lengths_host is not None else None,
        )
        return toks_h, counts_h, cache, dstate

    # -- prefill -------------------------------------------------------------

    def prefill_batch(self, tokens: np.ndarray, counts: np.ndarray,
                      slots: np.ndarray, starts: np.ndarray,
                      cache: PagedKVCache):
        """Advance all pending sequences by one chunk; returns (logits, cache).

        tokens (R, C) int32; counts/slots/starts (R,) host arrays.  Rows
        with ``counts == 0`` are padding.  The attention context is bounded
        by the mapped pages the furthest row needs, bucketed to the next
        power of two so the jit cache stays small.
        """
        counts = np.asarray(counts, np.int32)
        starts = np.asarray(starts, np.int32)
        slots = np.asarray(slots, np.int32)
        page = cache.page_size
        need = int(max(1, -(-int((starts + counts).max()) // page)))
        ctx = 1
        while ctx < need:
            ctx *= 2
        ctx = min(ctx, cache.pages_per_seq)
        fn = self._cached_program(
            (page, ctx), lambda: self._prefill(page, ctx)
        )
        real = counts > 0
        with span("engine.launch", kind="prefill", rows=int(real.sum()),
                  ctx_pages=ctx), _donation_noop_ok():
            logits, kp, vp, ks, vs, new_len = fn(
                self.params, jnp.asarray(tokens), jnp.asarray(counts),
                jnp.asarray(slots), jnp.asarray(starts),
                cache.k_pages, cache.v_pages, cache.k_scale, cache.v_scale,
                cache.page_table, cache.lengths,
            )
        lens_host = cache.lengths_host
        if lens_host is not None:
            lens_host = lens_host.copy()
            lens_host[slots[real]] = (starts + counts)[real]
        cache = dataclasses.replace(
            cache, k_pages=kp, v_pages=vp, k_scale=ks, v_scale=vs,
            lengths=new_len, lengths_host=lens_host,
        )
        return logits, cache

    def prefill_chunk(self, tokens, count: int, seq: int, start: int,
                      cache: PagedKVCache):
        """Single-sequence chunked prefill (the R=1 row of the batched path)."""
        logits, cache = self.prefill_batch(
            np.asarray(tokens, np.int32)[None, :],
            np.asarray([count], np.int32),
            np.asarray([seq], np.int32),
            np.asarray([start], np.int32),
            cache,
        )
        return logits[0], cache


class PagedFamily(ServableFamily):
    """:class:`ServableFamily` over a :class:`PagedLM` + :class:`PagedKVCache`.

    Resource units are physical pages.  Every method delegates to the
    exact calls the scheduler used to make directly, with identical
    argument values, so PagedLM serving output is bit-for-bit unchanged by
    the protocol indirection.

    The family is the *stateful* face of the functional cache: pool-mutating
    methods rebind ``self.cache`` to the returned cache, and the scheduler
    only ever reads the pool through the family (or its ``Scheduler.cache``
    compatibility property).
    """

    name = "paged"

    def __init__(self, model: PagedLM, cache: PagedKVCache):
        # Element width drives the math the model runs, so any model/cache
        # width mismatch (not just int8-vs-float) must fail loudly.
        if jnp.dtype(model.kv_dtype) != jnp.dtype(cache.k_pages.dtype):
            raise ValueError(
                f"model kv_dtype ({jnp.dtype(model.kv_dtype).name}) does not "
                f"match the cache pool dtype ({cache.k_pages.dtype.name}): "
                "create both with the same kv_dtype"
            )
        self.model = model
        self.cache = cache
        # Drafter state (speculative decoding): lazily initialized at the
        # first verify launch, then family-resident across launches.
        self._drafter_state = None

    # -- geometry -----------------------------------------------------------

    @property
    def batch(self) -> int:
        return self.cache.page_table.shape[0]

    @property
    def vocab(self) -> int:
        return self.model.cfg.vocab

    @property
    def total_units(self) -> int:
        return self.cache.total_pages

    @property
    def free_units(self) -> int:
        return self.cache.n_free

    @property
    def slot_token_capacity(self) -> int:
        return self.cache.pages_per_seq * self.cache.page_size

    @property
    def page_size(self) -> int:
        return self.cache.page_size

    def units_for(self, n_tokens: int) -> int:
        return self.cache.pages_for(n_tokens)

    def mapped_units(self, slot: int) -> int:
        return self.cache._mapped(slot)

    def token_capacity(self, slot: int) -> int:
        return self.cache._mapped(slot) * self.cache.page_size

    def lengths(self) -> np.ndarray:
        if self.cache.lengths_host is not None:
            return self.cache.lengths_host
        return np.asarray(self.cache.lengths)

    def _host_table(self) -> np.ndarray:
        if self.cache.page_table_host is not None:
            return self.cache.page_table_host
        return np.asarray(self.cache.page_table)

    # -- lifecycle ----------------------------------------------------------

    def alloc_state(self, slot: int, units: int) -> None:
        self.cache = self.cache.allocate(slot, units)

    def trim(self, slot: int, keep_units: int) -> None:
        self.cache = self.cache.trim(slot, keep_units)

    def release(self, slot: int) -> None:
        self.cache = self.cache.release(slot)

    # replay(): inherited no-op — freshly allocated pages hold no live KV,
    # so re-prefill after eviction rebuilds the slot from nothing already.

    # -- model compute ------------------------------------------------------

    def prefill_batch(self, tokens, counts, slots, starts):
        logits, self.cache = self.model.prefill_batch(
            tokens, counts, slots, starts, self.cache
        )
        return logits

    def decode_steps(self, tokens, active, n: int) -> np.ndarray:
        out, self.cache = self.model.decode_upto(
            tokens, self.cache, active, n
        )
        return out

    # -- speculative verify --------------------------------------------------

    @property
    def spec_k(self) -> int:
        return self.model.spec_k

    def verify_steps(self, tokens, active,
                     n: int) -> Tuple[np.ndarray, np.ndarray]:
        """``n`` fused draft→verify→accept launches over ``active`` slots.

        Returns ``(toks (n, B, spec_k), counts (n, B))`` host arrays —
        one sync at the boundary.  Drafter state is family-resident and
        carried across launches; it only shapes acceptance rate, so
        evictions/replays never need to snapshot or reset it for
        bit-exactness (they keep whatever it learned).
        """
        if self._drafter_state is None:
            self._drafter_state = self.model.drafter.init_state(self.batch)
        toks, counts, self.cache, self._drafter_state = \
            self.model.verify_upto(
                tokens, self.cache, active, n, self._drafter_state
            )
        return toks, counts

    # -- prefix sharing -----------------------------------------------------

    @property
    def supports_prefix_sharing(self) -> bool:
        return self.cache.refcounts is not None

    def share(self, slot: int, unit_ids: List[int]) -> None:
        self.cache = self.cache.share(slot, unit_ids)

    def retain_units(self, unit_ids: List[int]) -> None:
        self.cache = self.cache.retain_pages(unit_ids)

    def release_units(self, unit_ids: List[int]) -> None:
        self.cache = self.cache.release_pages(unit_ids)

    def unit_refcount(self, unit_id: int) -> int:
        return int(self.cache.refcounts[unit_id])

    def slot_unit_ids(self, slot: int) -> List[int]:
        row = self._host_table()[slot]
        return [int(p) for p in row[: self.cache._mapped(slot)]]

    def ensure_writable(self, slot: int, lo_token: int,
                        hi_token: int) -> int:
        self.cache, n_cow = self.cache.ensure_writable(
            slot, lo_token, hi_token
        )
        return n_cow

    # -- invariants ---------------------------------------------------------

    def check_integrity(self, retained: int = 0) -> None:
        self.cache.check_integrity(retained=retained)


def static_batch_generate(
    model: PagedLM,
    cache: PagedKVCache,
    prompts: Sequence[np.ndarray],
    max_new: int,
    chunk: int = 8,
) -> Dict[int, List[int]]:
    """Reference: all prompts prefilled up front, then one static decode batch.

    Uses the same jitted single-step prefill/decode building blocks the
    scheduler's fused fast path is made of (one-row ``prefill_batch`` calls,
    ``decode_step`` with host-side argmax), so scheduled continuous batching
    must reproduce these tokens bit-for-bit (asserted in
    tests/test_scheduler.py).  Requires a pool large enough to hold every
    sequence at once.
    """
    b = cache.page_table.shape[0]
    assert len(prompts) <= b, "static batch needs one slot per prompt"
    out: Dict[int, List[int]] = {}
    for i, prompt in enumerate(prompts):
        cache = cache.allocate(i, cache.pages_for(len(prompt) + max_new))
        toks: List[int] = []
        for start in range(0, len(prompt), chunk):
            count = min(chunk, len(prompt) - start)
            buf = np.zeros((chunk,), np.int32)
            buf[:count] = np.asarray(prompt)[start:start + count]
            logits, cache = model.prefill_chunk(
                jnp.asarray(buf), count, i, start, cache
            )
        toks.append(int(np.argmax(np.asarray(logits)[: model.cfg.vocab])))
        out[i] = toks
    for _ in range(max_new - 1):
        tokens = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        for i in range(len(prompts)):
            tokens[i] = out[i][-1]
            active[i] = True
        logits, cache = model.decode_step(
            jnp.asarray(tokens), cache, jnp.asarray(active)
        )
        nxt = np.argmax(np.asarray(logits)[:, : model.cfg.vocab], axis=-1)
        for i in range(len(prompts)):
            out[i].append(int(nxt[i]))
    return out
