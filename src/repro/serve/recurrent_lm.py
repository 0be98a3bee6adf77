"""Recurrent model families (RWKV6 / Mamba) behind the serving scheduler.

The transformer serving stack moves a *growing* KV cache through indirect
(page-table) bursts; recurrent architectures invert the memory story: each
sequence owns a **fixed-size** state vector, one slot per resident, laid out
``(layer, slot, *row)`` so a sequence's rows sit at a fixed stride of
``batch`` rows in the flattened pool.  That makes recurrent serving the
strided-burst dialect of AXI-Pack — no memory-resident index vector exists,
the stride in the request descriptor is the whole addressing metadata — and
the natural counterpart to compare against the paged families' indirect
accounting in ``BENCH_serving.json``.

Pieces:

* :class:`RecurrentLM` — a deliberately minimal tied-embedding LM over the
  real :func:`repro.models.rwkv6.rwkv_block` / :func:`repro.models.mamba
  .mamba_fwd` blocks.  **Every** token, prefill or decode, runs through one
  fused ``lax.scan`` program (:meth:`RecurrentLM._steps`) whose per-step
  body is identical regardless of trip count — the property that makes
  scheduler-served output bit-for-bit equal to a direct sequential forward
  at the same batch shape, no matter how chunked prefill and fused decode
  slice the token stream.  Inactive rows carry their state through
  ``jnp.where`` untouched (bit-exact), so batch composition never leaks
  between sequences.
* :class:`RecurrentStatePool` — the donated state pool + host bookkeeping
  (slot ownership, lengths), the recurrent analogue of
  :class:`repro.serve.kv.PagedKVCache`.
* :class:`RecurrentFamily` — the :class:`repro.serve.family.ServableFamily`
  implementation the scheduler drives: slots are the resource unit
  (``units_for(n) == 1``), capacity is unbounded so growth/lookahead never
  fire, eviction-replay re-prefills from a zeroed state row
  (:meth:`RecurrentFamily.replay`, via the strided state-write op), and the
  accounting dialect is :func:`repro.core.packing.recurrent_decode_traffic`
  + :func:`repro.core.streams.recurrent_state_streams`.
* :func:`recurrent_reference_generate` — the direct sequential forward the
  bitwise tests and the serving benchmark compare against.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.packing import (
    Traffic,
    recurrent_decode_traffic,
    recurrent_prefill_traffic,
)
from repro.core.streams import recurrent_state_streams
from repro.kernels import ops as kops
from repro.models import mamba as mamba_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.common import Param, init_params, rms_norm, stack_layer_defs
from repro.parallel.sharding import make_rules
from repro.runtime.tracing import span
from .family import OutOfPages, ServableFamily
from .kv import _donation_noop_ok

__all__ = [
    "RecurrentFamily",
    "RecurrentLM",
    "RecurrentStatePool",
    "recurrent_reference_generate",
]

#: Recurrent slots never grow: a sequence's state footprint is length-free,
#: so the per-slot token capacity is effectively unbounded and the
#: scheduler's growth / lookahead-prealloc machinery is statically idle.
UNBOUNDED_TOKENS = 1 << 62


@dataclasses.dataclass
class RecurrentStatePool:
    """Donated per-sequence state pools + host-side slot bookkeeping.

    ``tensors`` maps state name → a ``(n_layers, batch, *row)`` array (the
    layer-major layout the strided accounting assumes).  Device state is
    functional: every fused launch donates the pools and the family rebinds
    ``tensors``, exactly like the paged cache's page pools.
    """

    tensors: Dict[str, jax.Array]
    lengths_host: np.ndarray  # (batch,) int32 — tokens consumed per slot
    owned: np.ndarray         # (batch,) bool  — slot currently allocated

    @classmethod
    def create(cls, model: "RecurrentLM", batch: int) -> "RecurrentStatePool":
        tensors = {
            name: jnp.zeros((model.cfg.n_layers, batch) + shape, dtype)
            for name, (shape, dtype) in model.state_specs().items()
        }
        return cls(
            tensors=tensors,
            lengths_host=np.zeros((batch,), np.int32),
            owned=np.zeros((batch,), bool),
        )

    @property
    def batch(self) -> int:
        return int(self.lengths_host.shape[0])

    @property
    def n_layers(self) -> int:
        return int(next(iter(self.tensors.values())).shape[0])

    @property
    def n_free(self) -> int:
        return int(self.batch - self.owned.sum())

    @property
    def row_bytes(self) -> Tuple[int, ...]:
        """Per-layer row footprint of each state tensor (stream elements)."""
        lb = self.n_layers * self.batch
        return tuple(int(t.nbytes) // lb for t in self.tensors.values())

    @property
    def state_slot_bytes(self) -> int:
        """Bytes of one sequence's full state (all layers, all tensors)."""
        return sum(int(t.nbytes) // self.batch for t in self.tensors.values())

    @property
    def pool_bytes(self) -> int:
        return sum(int(t.nbytes) for t in self.tensors.values())


class RecurrentLM:
    """Minimal tied-embedding LM over real RWKV6 / Mamba blocks.

    Mirrors :class:`repro.serve.paged_lm.PagedLM`'s austerity (float32
    params, no final norm, greedy-friendly) so every per-token computation
    is row-wise — a sequence's outputs depend only on its own tokens and
    state rows, the property the scheduler's bitwise-equivalence guarantees
    rest on.  ``arch`` picks the block: ``'rwkv6'`` (wkv state per head) or
    ``'mamba'`` (SSM state + conv tail); both share all pool plumbing.
    """

    def __init__(self, cfg: ArchConfig, key: jax.Array,
                 arch: Optional[str] = None, impl: str = "pallas"):
        arch = arch or ("rwkv6" if cfg.ssm == "rwkv6" else "mamba")
        if arch not in ("rwkv6", "mamba"):
            raise ValueError(f"unknown recurrent arch: {arch!r}")
        if arch == "rwkv6" and cfg.d_model % rwkv_mod.HEAD_DIM:
            raise ValueError(
                f"rwkv6 needs d_model divisible by {rwkv_mod.HEAD_DIM}"
            )
        if arch == "mamba" and cfg.ssm_conv < 2:
            raise ValueError("mamba needs ssm_conv >= 2 (a conv state tail)")
        self.cfg = cfg
        self.arch = arch
        self.impl = impl
        self.rules = make_rules()
        d = cfg.d_model
        k_embed, k_layers = jax.random.split(key)
        self.embed = (
            jax.random.normal(k_embed, (cfg.vocab, d), jnp.float32) * 0.02
        )
        norm = lambda: Param((d,), ("d_model",), init="zeros")
        if arch == "rwkv6":
            ldefs: Dict[str, Any] = {
                **rwkv_mod.rwkv_defs(cfg), "ln1": norm(), "ln2": norm(),
            }
        else:
            ldefs = {"mamba": mamba_mod.mamba_defs(cfg), "ln": norm()}
        self.layers = init_params(stack_layer_defs(ldefs, cfg.n_layers),
                                  k_layers)

    def bind(self, pool: RecurrentStatePool) -> "RecurrentFamily":
        """Wrap this model + ``pool`` as the scheduler-facing family."""
        return RecurrentFamily(self, pool)

    def init_pool(self, batch: int) -> RecurrentStatePool:
        return RecurrentStatePool.create(self, batch)

    def state_specs(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """State name → (per-slot per-layer row shape, dtype)."""
        cfg = self.cfg
        d, hd = cfg.d_model, rwkv_mod.HEAD_DIM
        dt = cfg.compute_dtype
        if self.arch == "rwkv6":
            h = rwkv_mod.rwkv_heads(cfg)
            return {
                "s": ((h, hd, hd), jnp.float32),
                "x_tm": ((d,), dt),
                "x_cm": ((d,), dt),
            }
        di, _, n = mamba_mod.mamba_dims(cfg)
        return {
            "h": ((di, n), jnp.float32),
            "conv": ((cfg.ssm_conv - 1, d * 2), dt),
        }

    def _layer_step(self, p_l, x, st_l):
        """One layer over a (B, 1, D) slice; returns (x, new_layer_state)."""
        if self.arch == "rwkv6":
            return rwkv_mod.rwkv_block(p_l, x, self.cfg, self.rules, p_l, st_l)
        out, ns = mamba_mod.mamba_fwd(
            p_l["mamba"], rms_norm(x, p_l["ln"]), self.cfg, self.rules,
            st_l, chunk=1,
        )
        return x + out, ns

    @functools.cached_property
    def _steps(self):
        """The one fused token-step program (prefill *and* decode).

        ``lax.scan`` over ``n`` per-token steps; the body embeds the step's
        token (given for prefill, the carried greedy argmax for decode),
        runs every layer, and masks state write-back by ``active`` so
        inactive rows are bit-exact no-ops.  One body → one program shape
        per ``n``; scan bodies compile identically for every trip count, so
        any chunking of a token stream produces identical bits.
        """
        cfg, vocab = self.cfg, self.cfg.vocab
        n_layers = cfg.n_layers

        def recurrent_lm_steps(layers, embed, pool, cur, toks, use_input,
                               active):
            # pool: name → (L, B, *row); cur (B,) i32 carried token;
            # toks (n, B) i32; use_input (n,) bool; active (n, B) bool.
            def body(carry, xs):
                pool_c, cur_c = carry
                tok_in, use_in, act = xs
                tok = jnp.where(use_in, tok_in, cur_c)
                x = embed[tok][:, None, :].astype(cfg.compute_dtype)
                new_states = []
                for l in range(n_layers):
                    p_l = jax.tree.map(lambda a: a[l], layers)
                    st_l = {k: pool_c[k][l] for k in pool_c}
                    x, ns = self._layer_step(p_l, x, st_l)
                    new_states.append(ns)
                new_pool = {
                    k: jnp.stack([ns[k] for ns in new_states])
                    for k in pool_c
                }
                new_pool = {
                    k: jnp.where(
                        act.reshape((1, -1) + (1,) * (new_pool[k].ndim - 2)),
                        new_pool[k], pool_c[k],
                    )
                    for k in pool_c
                }
                logits = x[:, 0].astype(jnp.float32) @ embed.T  # (B, V)
                nxt = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
                cur_new = jnp.where(act, nxt, cur_c)
                return (new_pool, cur_new), (logits, cur_new)

            (pool_f, cur_f), (logits, toks_out) = jax.lax.scan(
                body, (pool, cur), (toks, use_input, active)
            )
            return pool_f, cur_f, logits, toks_out

        return jax.jit(recurrent_lm_steps, donate_argnums=(2,))

    def prefill_chunk(self, tensors, toks, active):
        """Feed given tokens: toks (C, B) i32, active (C, B) bool.

        Returns (new tensors, per-step logits (C, B, vocab) on device).
        """
        c, b = toks.shape
        cur = np.zeros((b,), np.int32)
        use = np.ones((c,), bool)
        rows = int(np.asarray(active).any(axis=0).sum())
        with span("engine.launch", kind="prefill", rows=rows), \
                _donation_noop_ok():
            tensors, _, logits, _ = self._steps(
                self.layers, self.embed, tensors, cur, toks, use, active
            )
        return tensors, logits

    def decode_chain(self, tensors, tokens, active, n: int):
        """Decode ``n`` greedy steps from current tokens; active (B,) bool.

        Power-of-two chaining (like ``PagedLM.decode_upto``) bounds the
        compiled-program count to O(log n); scan trip-count invariance makes
        the chain bit-identical to ``n`` single steps.  Returns
        (new tensors, (n, B) host tokens).
        """
        b = active.shape[0]
        cur = np.asarray(tokens, np.int32)
        outs: List[np.ndarray] = []
        rem = int(n)
        while rem:
            m = 1 << (rem.bit_length() - 1)
            toks = np.zeros((m, b), np.int32)
            use = np.zeros((m,), bool)
            act = np.broadcast_to(np.asarray(active, bool), (m, b))
            with span("engine.launch", kind="decode", n=m), \
                    _donation_noop_ok():
                tensors, cur, _, toks_out = self._steps(
                    self.layers, self.embed, tensors, cur, toks, use, act
                )
            with span("engine.sync", kind="decode"):
                outs.append(np.asarray(toks_out))
            rem -= m
        return tensors, np.concatenate(outs, axis=0)


class RecurrentFamily(ServableFamily):
    """Serve a :class:`RecurrentLM` out of a :class:`RecurrentStatePool`.

    The resource unit is the state *slot*: every sequence costs exactly one
    unit regardless of length (``units_for``), capacity never binds
    (``token_capacity`` is unbounded), and ``grow``/``trim`` are statically
    idle.  Eviction-replay is the same protocol the paged family uses —
    release the unit, re-admit, re-prefill — except the device half of the
    reset is explicit: :meth:`replay` zeroes the slot's state rows through
    the strided scatter op, since donated pools recycle rows across
    occupants.
    """

    def __init__(self, model: RecurrentLM, pool: RecurrentStatePool):
        want = set(model.state_specs())
        have = set(pool.tensors)
        if want != have:
            raise ValueError(
                f"state pool tensors {sorted(have)} do not match the "
                f"model's state layout {sorted(want)}: create the pool "
                f"with RecurrentStatePool.create(model, batch)"
            )
        self.model = model
        self.pool = pool
        self.name = model.arch

    # -- geometry -----------------------------------------------------------

    @property
    def batch(self) -> int:
        return self.pool.batch

    @property
    def vocab(self) -> int:
        return self.model.cfg.vocab

    @property
    def total_units(self) -> int:
        return self.pool.batch

    @property
    def free_units(self) -> int:
        return self.pool.n_free

    @property
    def slot_token_capacity(self) -> int:
        return UNBOUNDED_TOKENS

    @property
    def pool_bytes(self) -> int:
        return self.pool.pool_bytes

    def units_for(self, n_tokens: int) -> int:
        return 1 if n_tokens > 0 else 0

    def mapped_units(self, slot: int) -> int:
        return 1 if self.pool.owned[slot] else 0

    def token_capacity(self, slot: int) -> int:
        return UNBOUNDED_TOKENS if self.pool.owned[slot] else 0

    def state_bytes(self, n_tokens: int) -> int:
        return self.pool.state_slot_bytes if n_tokens > 0 else 0

    def lengths(self) -> np.ndarray:
        return self.pool.lengths_host

    # -- slot lifecycle -----------------------------------------------------

    def alloc_state(self, slot: int, units: int) -> None:
        if units <= 0:
            return
        if self.pool.owned[slot]:
            raise OutOfPages(f"slot {slot} is already allocated")
        if units > 1 or self.pool.n_free < 1:
            raise OutOfPages(
                f"need {units} state slot(s), {self.pool.n_free} free"
            )
        self.pool.owned[slot] = True
        self.pool.lengths_host[slot] = 0

    def release(self, slot: int) -> None:
        # Host bookkeeping only; the stale device rows are zeroed by the
        # next occupant's replay() at admission.
        self.pool.owned[slot] = False
        self.pool.lengths_host[slot] = 0

    def replay(self, slot: int) -> None:
        """Zero the slot's state rows (strided scatter) — fresh-prefill
        semantics for recycled donated pools; called at every admission."""
        for name, t in self.pool.tensors.items():
            zeros = jnp.zeros((t.shape[0],) + t.shape[2:], t.dtype)
            self.pool.tensors[name] = kops.recurrent_state_write(
                t, int(slot), zeros, impl=self.model.impl
            )
        self.pool.lengths_host[slot] = 0

    # -- compute ------------------------------------------------------------

    def prefill_batch(self, tokens: np.ndarray, counts: np.ndarray,
                      slots: np.ndarray, starts: np.ndarray) -> np.ndarray:
        b = self.batch
        n_rows, c = tokens.shape
        toks = np.zeros((c, b), np.int32)
        act = np.zeros((c, b), bool)
        for i in range(n_rows):
            ci, si = int(counts[i]), int(slots[i])
            toks[:ci, si] = tokens[i, :ci]
            act[:ci, si] = True
        self.pool.tensors, logits = self.model.prefill_chunk(
            self.pool.tensors, toks, act
        )
        with span("engine.sync", kind="prefill"):
            lg = np.asarray(logits)  # (C, B, vocab)
        out = lg[np.maximum(np.asarray(counts, np.int64) - 1, 0),
                 np.asarray(slots, np.int64)]
        # Scalar loop: padding rows alias slot 0 with count 0, and fancy
        # `+=` drops duplicate-index updates instead of accumulating them.
        for i in range(n_rows):
            self.pool.lengths_host[int(slots[i])] += int(counts[i])
        return out

    def decode_steps(self, tokens: np.ndarray, active: np.ndarray,
                     n: int) -> np.ndarray:
        self.pool.tensors, out = self.model.decode_chain(
            self.pool.tensors, tokens, active, n
        )
        self.pool.lengths_host[np.asarray(active, bool)] += int(n)
        return out

    # -- accounting ---------------------------------------------------------

    def step_streams(self, active: np.ndarray,
                     n: int) -> List[Tuple[Traffic, tuple]]:
        slots = [int(s) for s in np.nonzero(np.asarray(active, bool))[0]]
        traffic = recurrent_decode_traffic(
            len(slots), self.batch, self.pool.state_slot_bytes
        )
        streams = recurrent_state_streams(
            slots, self.batch, self.pool.n_layers, self.pool.row_bytes
        )
        # State size is length-free, so every fused step moves identical
        # bytes — one record shared n times, like a step-at-a-time run.
        return [(traffic, streams)] * int(n)

    def prefill_account(self, slots: np.ndarray, starts: np.ndarray,
                        counts: np.ndarray) -> Tuple[Traffic, tuple]:
        traffic = recurrent_prefill_traffic(
            counts, self.batch, self.pool.state_slot_bytes
        )
        streams = recurrent_state_streams(
            [int(s) for s in slots], self.batch, self.pool.n_layers,
            self.pool.row_bytes,
        )
        return traffic, streams

    # -- invariants ---------------------------------------------------------

    def check_integrity(self, retained: int = 0) -> None:
        if retained:
            raise ValueError(
                f"recurrent family cannot hold {retained} retained prefix "
                f"entries (no prefix sharing)"
            )
        pool = self.pool
        if pool.lengths_host.shape != (pool.batch,):
            raise ValueError("lengths shadow shape mismatch")
        bad = np.nonzero(~pool.owned & (pool.lengths_host != 0))[0]
        if bad.size:
            raise ValueError(
                f"free slots {bad.tolist()} have nonzero lengths"
            )
        if (pool.lengths_host < 0).any():
            raise ValueError("negative slot length")
        for name, t in pool.tensors.items():
            if t.shape[:2] != (pool.n_layers, pool.batch):
                raise ValueError(
                    f"state tensor {name!r} has pool shape {t.shape[:2]}, "
                    f"want {(pool.n_layers, pool.batch)}"
                )


def recurrent_reference_generate(
    model: RecurrentLM,
    pool: RecurrentStatePool,
    prompts: Sequence[Sequence[int]],
    max_new: int,
    chunk: int = 8,
) -> List[List[int]]:
    """Direct sequential forward: the serving-free ground truth.

    Drives the same fused step program over the same batch shape — prompt
    tokens per-position with row masks, then greedy decode — with no
    scheduler in the loop.  Scan-chunking invariance and bit-exact row
    masking make the result identical to any scheduler interleaving, so
    tests and the benchmark assert bitwise equality against this.
    """
    b = pool.batch
    if len(prompts) > b:
        raise ValueError(f"{len(prompts)} prompts > batch {b}")
    plens = [len(p) for p in prompts]
    if min(plens, default=1) < 1:
        raise ValueError("empty prompt")
    tensors = pool.tensors
    maxp = max(plens)
    last = np.zeros((len(prompts), model.cfg.vocab), np.float32)
    pos = 0
    while pos < maxp:
        c = min(chunk, maxp - pos)
        toks = np.zeros((c, b), np.int32)
        act = np.zeros((c, b), bool)
        for i, p in enumerate(prompts):
            ci = min(max(plens[i] - pos, 0), c)
            if ci:
                toks[:ci, i] = p[pos:pos + ci]
                act[:ci, i] = True
        tensors, logits = model.prefill_chunk(tensors, toks, act)
        lg = np.asarray(logits)
        for i in range(len(prompts)):
            if pos < plens[i] <= pos + c:
                last[i] = lg[plens[i] - pos - 1, i, :model.cfg.vocab]
        pos += c
    out = [[int(np.argmax(last[i]))] for i in range(len(prompts))]
    if max_new > 1:
        tokens = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        for i, o in enumerate(out):
            tokens[i] = o[0]
            active[i] = True
        tensors, steps = model.decode_chain(tensors, tokens, active,
                                            max_new - 1)
        for i, o in enumerate(out):
            o.extend(int(steps[s, i]) for s in range(max_new - 1))
    pool.tensors = tensors
    return out
