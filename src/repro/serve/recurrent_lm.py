"""Recurrent model families (RWKV6 / Mamba) behind the serving scheduler.

The transformer serving stack moves a *growing* KV cache through indirect
(page-table) bursts; recurrent architectures invert the memory story: each
sequence owns a **fixed-size** state vector, one slot per resident, in
pools laid out ``(layer, slot, *row)``.

Pieces:

* :class:`RecurrentLM` — the published RWKV6 "Finch" model
  (:mod:`repro.models.rwkv6`: ``ln0``, the blocks, ``ln_out``, an untied
  head), or a tied-embedding LM over :func:`repro.models.mamba.mamba_fwd`.
  Prefill (:meth:`RecurrentLM.prefill_chunk`) gathers the call's own state
  rows from the pool, runs each RWKV6 layer once over the whole
  ``(rows, chunk)`` grid (the weights read once a call; only the WKV
  recurrence steps through the chunk, a block of positions at a time, on
  the gathered rows), and scatters the rows back in place; padding
  positions and padding rows move no state.
  Decode (:meth:`RecurrentLM.decode_chain`) runs one per-token body over
  every slot inside a ``lax.scan``, identical regardless of trip count,
  with inactive rows carried through ``jnp.where`` untouched (bit-exact).
  Rows outside a call keep their state bit for bit, so batch composition
  never leaks between sequences; a sequence's outputs depend on its own
  tokens and on how its prompt is cut into chunks.
* :class:`RecurrentStatePool` — the donated state pool + host bookkeeping
  (slot ownership, lengths), the recurrent analogue of
  :class:`repro.serve.kv.PagedKVCache`.
* :class:`RecurrentFamily` — the :class:`repro.serve.family.ServableFamily`
  implementation the scheduler drives: slots are the resource unit
  (``units_for(n) == 1``), capacity is unbounded so growth/lookahead never
  fire, eviction-replay re-prefills from a zeroed state row (the prefill
  program zeroes each row it starts at position 0, so
  :meth:`RecurrentFamily.replay` launches nothing).
* :func:`recurrent_reference_generate` — the direct forward, with no
  scheduler, that the tests compare against.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import mamba as mamba_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.common import Param, init_params, rms_norm, stack_layer_defs
from repro.parallel.sharding import make_rules
from repro.runtime.tracing import span
from .family import OutOfPages, ServableFamily
from .kv import _donation_noop_ok
from .scheduler import build_prefill_rows

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

    ``tensors`` maps state name → a ``(n_layers, batch, *row)`` array
    (layer-major).  Device state is
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


class RecurrentLM:
    """A language model over RWKV6 or Mamba blocks, served from state slots.

    ``arch='rwkv6'`` is the published Finch model (:mod:`repro.models.rwkv6`):
    ``ln0`` on the embedding, the layers, ``ln_out`` and an untied head.
    ``arch='mamba'`` keeps a tied embedding around
    :func:`repro.models.mamba.mamba_fwd` layers.  ``params`` (the tree of
    :meth:`param_defs`) replaces the weights drawn from ``key``.  Parameters
    are kept in ``cfg.param_dtype``, activations in ``cfg.dtype``; state and
    decay are float32.

    Two programs serve it.  :meth:`prefill_chunk` takes a call's ``(R, C)``
    rows of given tokens: it gathers the R slots' state rows (zeros for a
    row that starts its sequence), runs RWKV6's layers each once over the
    chunk, a per-position mask keeping padding out of the state (Mamba runs
    its per-token body over the gathered rows), scatters the rows back into
    the donated pool, and runs the head on each row's last fed position.
    :meth:`decode_chain` runs one per-token body (embed, all layers, state
    write-back masked by the row's ``active`` flag) over every slot in a
    ``lax.scan``, with the head and greedy argmax every step; tokens out.
    No per-step logits stack is made.  A sequence's outputs depend only on
    its own tokens, state rows and prompt chunks, so scheduler-served
    tokens equal :func:`recurrent_reference_generate` fed the same chunks.
    """

    def __init__(self, cfg: ArchConfig, key: Optional[jax.Array],
                 arch: Optional[str] = None,
                 params: Optional[Dict[str, Any]] = None):
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
        self.rules = make_rules()
        if params is None:
            dtype = jnp.dtype(cfg.param_dtype)
            params = init_params(self.param_defs(), key, dtype)
        self.params = params

    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        d = cfg.d_model
        if self.arch == "rwkv6":
            return {**rwkv_mod.rwkv_lm_defs(cfg), "layers": stack_layer_defs(
                rwkv_mod.rwkv_defs(cfg), cfg.n_layers)}
        return {
            "embed": Param((cfg.vocab, d), ("vocab", "d_model"), scale=0.02),
            "layers": stack_layer_defs(
                {"mamba": mamba_mod.mamba_defs(cfg),
                 "ln": Param((d,), ("d_model",), init="zeros")},
                cfg.n_layers),
        }

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

    # -- the per-token body ---------------------------------------------------

    def _embed(self, params, tok):
        """(B,) token ids → (B, D) activations."""
        dt = self.cfg.compute_dtype
        if self.arch == "rwkv6":
            return rwkv_mod.rwkv_embed(params, tok, dt)
        return params["embed"][tok].astype(dt)

    def _head(self, params, x):
        """(B, D) activations → (B, vocab) float32 logits."""
        if self.arch == "rwkv6":
            return rwkv_mod.rwkv_logits(params, x)
        return jnp.dot(x, params["embed"].T.astype(x.dtype),
                       preferred_element_type=jnp.float32)

    def _layer_step(self, p_l, x, st_l):
        """One layer over a (B, 1, D) slice; returns (x, new_layer_state)."""
        if self.arch == "rwkv6":
            return rwkv_mod.rwkv_block(p_l, x, self.cfg, self.rules, st_l)
        out, ns = mamba_mod.mamba_fwd(
            p_l["mamba"], rms_norm(x, p_l["ln"]), self.cfg, self.rules,
            st_l, chunk=1,
        )
        return x + out, ns

    def _token(self, params, pool, tok, act):
        """One token for every row: (B,) ids, (B,) bool → ((B, D) output
        activations, pool with the active rows' state advanced)."""
        x = self._embed(params, tok)[:, None, :]
        new_states = []
        for l in range(self.cfg.n_layers):
            p_l = jax.tree.map(lambda a: a[l], params["layers"])
            st_l = {k: pool[k][l] for k in pool}
            x, ns = self._layer_step(p_l, x, st_l)
            new_states.append(ns)
        new_pool = {}
        for k in pool:
            stacked = jnp.stack([ns[k] for ns in new_states])
            mask = act.reshape((1, -1) + (1,) * (stacked.ndim - 2))
            new_pool[k] = jnp.where(mask, stacked, pool[k])
        return x[:, 0], new_pool

    # -- programs ------------------------------------------------------------

    def _chunk(self, params, pool, toks, valid, slots, fresh):
        """RWKV6 over a call's rows: each layer once over the (R, C) grid,
        its rows gathered from the pool and scattered back a layer at a
        time.  Returns ((R, C, D) activations, the pool)."""
        x = self._embed(params, toks)
        for l in range(self.cfg.n_layers):
            p_l = jax.tree.map(lambda a: a[l], params["layers"])
            rows = {k: _gather_rows(t[l:l + 1], slots, fresh)[0]
                    for k, t in pool.items()}
            x, ns = rwkv_mod.rwkv_block(p_l, x, self.cfg, self.rules, rows,
                                        valid)
            pool = {k: t.at[l:l + 1].set(_scatter_rows(
                t[l:l + 1], ns[k][None], slots, valid[:, 0]))
                for k, t in pool.items()}
        return x, pool

    def _token_serial(self, params, pool, toks, valid, slots, fresh):
        """The per-token body over a call's rows, position by position.
        Returns ((R, C, D) activations, the pool)."""
        def body(rows_c, xs):
            tok, ok = xs
            x, rows_c = self._token(params, rows_c, tok, ok)
            return rows_c, x

        rows = {k: _gather_rows(t, slots, fresh) for k, t in pool.items()}
        rows, xs = jax.lax.scan(body, rows, (toks.T, valid.T))
        pool = {k: _scatter_rows(t, rows[k], slots, valid[:, 0])
                for k, t in pool.items()}
        return jnp.swapaxes(xs, 0, 1), pool

    @functools.cached_property
    def _prefill(self):
        def recurrent_lm_prefill(params, pool, toks, counts, slots, fresh):
            # toks (R, C); counts, slots (R,) i32; fresh (R,) bool: the
            # row starts its sequence and reads a zero state.  A row with
            # count 0 is padding (``valid[:, 0]`` False) and writes
            # nothing.  Returns the pool and (R, vocab) logits at each
            # row's last fed position.
            valid = jnp.arange(toks.shape[1])[None, :] < counts[:, None]
            run = self._chunk if self.arch == "rwkv6" else self._token_serial
            x, pool = run(params, pool, toks, valid, slots, fresh)
            last = jnp.maximum(counts - 1, 0)[:, None, None]
            x_last = jnp.take_along_axis(x, last, axis=1)[:, 0]
            return pool, self._head(params, x_last)

        return jax.jit(recurrent_lm_prefill, donate_argnums=(1,))

    @functools.cached_property
    def _decode(self):
        def recurrent_lm_decode(params, pool, cur, active, n):
            # cur (B,) i32 carried token; active (B,) bool.  Returns the
            # pool, the carried tokens and (n, B) tokens.
            def body(carry, _):
                pool_c, cur_c = carry
                x, pool_c = self._token(params, pool_c, cur_c, active)
                nxt = jnp.argmax(self._head(params, x),
                                 axis=-1).astype(jnp.int32)
                cur_c = jnp.where(active, nxt, cur_c)
                return (pool_c, cur_c), cur_c

            (pool, cur), toks = jax.lax.scan(body, (pool, cur), None,
                                             length=n)
            return pool, cur, toks

        return jax.jit(recurrent_lm_decode, donate_argnums=(1,),
                       static_argnums=(4,))

    def prefill_chunk(self, tensors, toks, counts, slots, fresh):
        """Feed given tokens to a call's rows: toks (R, C), row i feeding
        its first ``counts[i]`` tokens to slot ``slots[i]``; a row with
        count 0 is padding and writes nothing.  Rows flagged in ``fresh``
        (R,) start their sequence here: their state is zero at their first
        token, whatever their pool rows hold.  Returns (new tensors,
        (R, vocab) logits on the device): those of each row's last fed
        position."""
        toks = np.asarray(toks, np.int32)
        counts = np.asarray(counts, np.int32)
        with span("engine.launch", kind="prefill",
                  rows=int((counts > 0).sum()),
                  row_fill=float(counts.sum() / toks.size)), \
                _donation_noop_ok():
            return self._prefill(self.params, tensors, toks, counts,
                                 np.asarray(slots, np.int32),
                                 np.asarray(fresh, bool))

    def decode_chain(self, tensors, tokens, active, n: int):
        """Decode ``n`` greedy steps from current tokens; active (B,) bool.

        Power-of-two chaining (like ``PagedLM.decode_upto``) bounds the
        compiled-program count to O(log n); scan trip-count invariance makes
        the chain bit-identical to ``n`` single steps.  Returns (new
        tensors, (n, B) host tokens).
        """
        active = np.asarray(active, bool)
        cur = np.asarray(tokens, np.int32)
        fill = float(active.mean())
        outs: List[np.ndarray] = []
        rem = int(n)
        while rem:
            m = 1 << (rem.bit_length() - 1)
            with span("engine.launch", kind="decode", n=m, row_fill=fill), \
                    _donation_noop_ok():
                tensors, cur, toks_out = self._decode(
                    self.params, tensors, cur, active, m
                )
            with span("engine.sync", kind="decode"):
                outs.append(np.asarray(toks_out))
            rem -= m
        return tensors, np.concatenate(outs, axis=0)


def _slot_hits(slots, batch):
    """(R, batch) bool: row i's one-hot slot."""
    return slots[:, None] == jnp.arange(batch)[None, :]


def _gather_rows(t, slots, fresh):
    """``t[:, slots]`` of a ``(L, batch, *row)`` pool, zeros for the rows
    flagged in ``fresh``.

    A one-hot product over the slot axis (exact: one nonzero term), not an
    indexed gather: the chip lays a state pool's slot axis in its lanes,
    where an indexed gather or scatter first copies the whole pool into
    another layout.  A non-finite row anywhere in the pool would reach
    every gathered row (``0 · inf``); the state stays finite.
    """
    hit = _slot_hits(slots, t.shape[1]) & ~fresh[:, None]
    return jnp.einsum("lb...,rb->lr...", t, hit.astype(t.dtype),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=t.dtype)


def _scatter_rows(t, rows, slots, keep):
    """``t`` with ``rows[:, i]`` written to slot ``slots[i]`` where
    ``keep[i]``, in place.  A padding row (``keep`` False) writes nothing,
    so it never races a real row on the slot it aliases; every other slot
    keeps its rows bit for bit."""
    hit = _slot_hits(slots, t.shape[1]) & keep[:, None]
    spread = jnp.einsum("lr...,rb->lb...", rows, hit.astype(t.dtype),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=t.dtype)
    written = hit.any(axis=0).reshape((1, -1) + (1,) * (t.ndim - 2))
    return jnp.where(written, spread, t)


class RecurrentFamily(ServableFamily):
    """Serve a :class:`RecurrentLM` out of a :class:`RecurrentStatePool`.

    The resource unit is the state *slot*: every sequence costs exactly one
    unit regardless of length (``units_for``), capacity never binds
    (``token_capacity`` is unbounded), and ``grow``/``trim`` are statically
    idle.  Eviction-replay is the same protocol the paged family uses —
    release the unit, re-admit, re-prefill.  Donated pools recycle rows
    across occupants, so a row's prefill from position 0 reads a zero state
    instead of its stale pool rows (``fresh`` in :meth:`prefill_batch`).
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

    def units_for(self, n_tokens: int) -> int:
        return 1 if n_tokens > 0 else 0

    def mapped_units(self, slot: int) -> int:
        return 1 if self.pool.owned[slot] else 0

    def token_capacity(self, slot: int) -> int:
        return UNBOUNDED_TOKENS if self.pool.owned[slot] else 0

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
        # Host bookkeeping only; the next occupant's first prefill chunk
        # reads a zero state in place of the stale device rows.
        self.pool.owned[slot] = False
        self.pool.lengths_host[slot] = 0

    def replay(self, slot: int) -> None:
        """Fresh-prefill semantics at every admission: the slot's length
        restarts at 0, and its prefill from position 0 zeroes its state on
        the device (:meth:`prefill_batch`), so nothing is launched here."""
        self.pool.lengths_host[slot] = 0

    # -- compute ------------------------------------------------------------

    def prefill_batch(self, tokens: np.ndarray, counts: np.ndarray,
                      slots: np.ndarray, starts: np.ndarray) -> np.ndarray:
        self.pool.tensors, logits = self.model.prefill_chunk(
            self.pool.tensors, tokens, counts, slots, np.asarray(starts) == 0
        )
        with span("engine.sync", kind="prefill"):
            out = np.asarray(logits)  # (rows, vocab)
        # Scalar loop: padding rows alias slot 0 with count 0, and fancy
        # `+=` drops duplicate-index updates instead of accumulating them.
        for i in range(len(counts)):
            self.pool.lengths_host[int(slots[i])] += int(counts[i])
        return out

    def decode_steps(self, tokens: np.ndarray, active: np.ndarray,
                     n: int) -> np.ndarray:
        self.pool.tensors, out = self.model.decode_chain(
            self.pool.tensors, tokens, active, n
        )
        self.pool.lengths_host[np.asarray(active, bool)] += int(n)
        return out

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
    """Direct forward: the serving-free ground truth.

    Feeds every prompt through the prefill program in chunks of ``chunk``
    tokens from its start (one call per chunk position, the rows bucketed
    as the scheduler buckets them), then greedy decode over the same batch
    of slots, with no scheduler in the loop.  A sequence's outputs depend
    only on its own tokens and on how its prompt is cut, so a scheduler of
    the same ``chunk`` serves the same tokens whatever its interleaving.
    """
    b = pool.batch
    if len(prompts) > b:
        raise ValueError(f"{len(prompts)} prompts > batch {b}")
    plens = [len(p) for p in prompts]
    if min(plens, default=1) < 1:
        raise ValueError("empty prompt")
    tensors = pool.tensors
    maxp = max(plens)
    out: List[List[int]] = [[] for _ in prompts]
    for pos in range(0, maxp, chunk):
        items = [(p, pos, i) for i, p in enumerate(prompts) if pos < plens[i]]
        toks, counts, slots, starts = build_prefill_rows(items, chunk, b)
        tensors, logits = model.prefill_chunk(tensors, toks, counts, slots,
                                              starts == 0)
        lg = np.asarray(logits)
        for j, (_, _, i) in enumerate(items):
            if plens[i] <= pos + chunk:
                out[i].append(int(np.argmax(lg[j])))
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
