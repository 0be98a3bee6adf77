"""RWKV6 "Finch" block: data-dependent-decay time mix + channel mix.

The published block (arXiv:2404.05892; RWKV/v6-Finch-*-HF, RWKV-LM v6),
one definition for training (:mod:`repro.models.lm`), serving
(:class:`repro.serve.RecurrentLM`) and the tests:

* time mix on ``a = LayerNorm_1(x)`` (weight and bias, eps 1e-5): token
  shift ``sx = a_{t-1} - a``; the five inputs ``a + sx·(mu_i + tanh(xxx
  W1_i) W2_i)`` with ``xxx = a + sx·mu_x`` (rank-32 LoRA, order w, k, v, r,
  g); ``r, k, v = x_r R, x_k K, x_v V``, ``g = silu(x_g G)``; the decay
  ``w = exp(-exp(time_decay + tanh(x_w D1) D2))`` (rank-64 LoRA,
  :func:`decay`); the WKV recurrence

      S_t = diag(w_t) S_{t-1} + k_t v_tᵀ,   y_t = r_t·(S_{t-1} + diag(u) k_t v_tᵀ)

  over heads of 64 with a float32 64×64 state per head; a GroupNorm over the
  heads (weight and bias, eps ``1e-5 · head_size_divisor²``); ``(y ⊙ g) O``;
* channel mix on ``c = LayerNorm_2(x)``: ``sigmoid(x_r Wr) ⊙ (relu(x_k Wk)²
  Wv)`` with the token-shifted lerps ``x_k``, ``x_r``;
* around the layers (:func:`rwkv_lm_defs`): ``ln0`` on the embedding,
  ``ln_out`` before an untied head.

The recurrence is evaluated three ways that agree: :func:`wkv6`, the
chunked decayed-cumsum form (exact, differentiable, O(chunk·H·hd²) live
memory) for whole sequences; :func:`wkv6_step`, one token, which decode
runs; and :func:`wkv6_masked`, blocks of positions in closed form over a
chunk whose rows end at their own position, which serving's prefill runs
(each layer once over the whole chunk, a ``valid`` mask marking the
positions that move the state; O(block²·H·hd) live memory).  Decay and
state are float32 whatever the compute dtype.

Departures from the published model:

* ``rescale_every`` (halving the residual every few layers at inference) is
  an fp16 overflow guard that is the identity in exact arithmetic; it is
  left out.
* :mod:`repro.models.lm`'s training path keeps its own embedding and final
  RMSNorm around these blocks (no ``ln0``, ``ln_out`` as an RMSNorm);
  serving uses :func:`rwkv_lm_defs`.
* The LoRA factors are stored per input (``lora_a`` (5, D, 32)) rather
  than as one (D, 160) matrix: the same products, in training's layout.

TP: time-mix projections are head-shaped (d → H×64) and shard over 'model'
like attention heads (padded 40→48 under TP-16, recorded in the config).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.parallel.sharding import ShardingRules, constrain
from .common import Param, decayed_cumsum, layer_norm

#: Rank of the five mix LoRAs (RWKV-LM v6 TIME_MIX_EXTRA_DIM below 4096 wide).
LORA_R = 32
#: Rank of the decay LoRA (TIME_DECAY_EXTRA_DIM below 4096 wide).
DECAY_R = 64
HEAD_DIM = 64
#: Finch's ``head_size_divisor``: it sets the head GroupNorm's eps.
HEAD_SIZE_DIVISOR = 8
LN_EPS = 1e-5
GN_EPS = LN_EPS * HEAD_SIZE_DIVISOR ** 2
MIX_NAMES = ("w", "k", "v", "r", "g")


def rwkv_heads(cfg: ArchConfig, padded: bool = False) -> int:
    if padded and cfg.tp_pad_heads:
        return cfg.tp_pad_heads
    return cfg.d_model // HEAD_DIM


def _norm_defs(shape, dims) -> Dict[str, Param]:
    return {"w": Param(shape, dims, init="ones"),
            "b": Param(shape, dims, init="zeros")}


def rwkv_defs(cfg: ArchConfig, heads: Optional[int] = None) -> Dict[str, Any]:
    """One layer: ``ln1``, ``ln2``, time mix ``tm`` and channel mix ``cm``."""
    d, f = cfg.d_model, cfg.d_ff
    h = heads or rwkv_heads(cfg)
    head = (h, HEAD_DIM)
    tm = {
        "mu_x": Param((d,), ("d_model",), init="zeros"),
        "mu": Param((5, d), (None, "d_model"), init="zeros"),
        "lora_a": Param((5, d, LORA_R), (None, "d_model", None), scale=0.01),
        "lora_b": Param((5, LORA_R, d), (None, None, "d_model"), scale=0.01),
        "w_base": Param(head, ("heads", "head_dim"), init="zeros"),
        "wa": Param((d, DECAY_R), ("d_model", None), scale=0.01),
        "wb": Param((DECAY_R, h, HEAD_DIM), (None, "heads", "head_dim"),
                    scale=0.01),
        "u": Param(head, ("heads", "head_dim"), init="zeros"),
        "wr": Param((d, h, HEAD_DIM), ("fsdp", "heads", "head_dim")),
        "wk": Param((d, h, HEAD_DIM), ("fsdp", "heads", "head_dim")),
        "wv": Param((d, h, HEAD_DIM), ("fsdp", "heads", "head_dim")),
        "wg": Param((d, h, HEAD_DIM), ("fsdp", "heads", "head_dim")),
        "wo": Param((h, HEAD_DIM, d), ("heads", "head_dim", "fsdp")),
        "ln_x": _norm_defs(head, ("heads", "head_dim")),
    }
    cm = {
        "mu_k": Param((d,), ("d_model",), init="zeros"),
        "mu_r": Param((d,), ("d_model",), init="zeros"),
        "wk": Param((d, f), ("fsdp", "d_ff")),
        "wv": Param((f, d), ("d_ff", "fsdp")),
        # receptance gate output dim shards over the model axis ('heads'):
        # replicated it costs d² per layer in params+grads+moments.
        "wr": Param((d, d), ("fsdp", "heads")),
    }
    return {"tm": tm, "cm": cm,
            "ln1": _norm_defs((d,), ("d_model",)),
            "ln2": _norm_defs((d,), ("d_model",))}


def rwkv_lm_defs(cfg: ArchConfig) -> Dict[str, Any]:
    """Around the layers: embedding with ``ln0``, ``ln_out`` and an untied
    head."""
    d, v = cfg.d_model, cfg.vocab
    return {
        "embed": Param((v, d), ("vocab", "d_model"), init="embed"),
        "ln0": _norm_defs((d,), ("d_model",)),
        "ln_out": _norm_defs((d,), ("d_model",)),
        "head": Param((d, v), ("d_model", "vocab")),
    }


def _ln(x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    return layer_norm(x, p["w"], p["b"], LN_EPS)


def rwkv_embed(p, tokens: jax.Array, dtype) -> jax.Array:
    """``ln0(E[tokens])`` in ``dtype``."""
    return _ln(p["embed"][tokens].astype(dtype), p["ln0"])


def rwkv_logits(p, x: jax.Array) -> jax.Array:
    """Float32 logits ``ln_out(x) H`` of activations ``x`` (..., D)."""
    x = _ln(x, p["ln_out"])
    return jnp.dot(x, p["head"].astype(x.dtype),
                   preferred_element_type=jnp.float32)


def _shift(x: jax.Array, x_last: Optional[jax.Array]) -> jax.Array:
    """Token shift: previous token's activation (zeros / carried at t=0)."""
    prev = jnp.zeros_like(x[:, :1]) if x_last is None else x_last[:, None]
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def wkv6(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
    u: jax.Array, s0: jax.Array, chunk: int = 32,
) -> Tuple[jax.Array, jax.Array]:
    """r,k,v,w: (B,T,H,hd); u: (H,hd); s0: (B,H,hd,hd) → (y f32, s_final).

    The chunk step is rematerialized (jax.checkpoint): backward keeps only
    the per-chunk state carry (B·H·hd² f32) and recomputes the chunk-local
    (C,B,H,hd,hd) tensors — without this, training a 4k sequence would
    retain ~T/C × C·B·H·hd² bytes of scan residuals (observed 62 GB/device
    on the rwkv6-3b dry-run; 3.4 GB after — EXPERIMENTS.md §Perf).
    """
    b, t, h, hd = r.shape
    chunk = min(chunk, t)
    assert t % chunk == 0
    n = t // chunk

    def to_chunks(x):
        return x.reshape(b, n, chunk, h, hd).transpose(1, 2, 0, 3, 4)

    rc, kc, vc, wc = map(to_chunks, (r, k, v, w))  # (n, C, B, H, hd)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def step(s, xs):
        rcc, kcc, vcc, wcc = (x.astype(jnp.float32) for x in xs)
        a = jnp.broadcast_to(wcc[..., None], wcc.shape + (hd,))
        bb = kcc[..., None] * vcc[..., None, :]
        hs, s_new = decayed_cumsum(a, bb, s, chunk=a.shape[0])
        s_prev = jnp.concatenate([s[None], hs[:-1]], axis=0)
        y = jnp.einsum("cbhk,cbhkv->cbhv", rcc, s_prev)
        bonus = jnp.einsum("cbhk,hk,cbhk->cbh", rcc, u.astype(jnp.float32), kcc)
        y = y + bonus[..., None] * vcc
        return s_new, y

    s_final, ys = jax.lax.scan(step, s0.astype(jnp.float32), (rc, kc, vc, wc))
    y = ys.transpose(2, 0, 1, 3, 4).reshape(b, t, h, hd)
    return y, s_final


#: Positions per step of :func:`wkv6_masked`'s scan.
MASKED_BLOCK = 16


def wkv6_masked(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
    u: jax.Array, s0: jax.Array, valid: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over T positions, some of them padding: r,k,v,w
    (B,T,H,hd); u (H,hd); s0 (B,H,hd,hd) float32; valid (B,T) bool →
    (y (B,T,H,hd) float32, state).

    A position where ``valid`` is False is an identity step (``k = 0``,
    decay 1): it leaves the state as it was, and its ``y`` means nothing.
    A scan steps over blocks of n positions (the largest divisor of T up
    to :data:`MASKED_BLOCK`), each in closed form with float32 products:
    position t reads the carried state through the decay since the block
    began, and each earlier position s of the block through
    ``exp(Σ_{s<τ<t} log w_τ)``, every exponent at most 0.  Only the state
    and one block's (B,H,n,n,hd) decays are live, never a state per
    position.
    """
    b, t, h, hd = r.shape
    n = max(d for d in range(1, min(MASKED_BLOCK, t) + 1) if t % d == 0)
    f32 = jnp.float32
    ok = valid[..., None, None]
    k = jnp.where(ok, k, jnp.zeros((), k.dtype))
    lw = jnp.where(ok, jnp.log(jnp.maximum(w, jnp.finfo(f32).tiny)), 0.0)

    def blocks(a):  # (B, T, H, hd) -> (T/n, B, H, n, hd)
        return a.reshape(b, t // n, n, h, hd).transpose(1, 0, 3, 2, 4)

    hi = jax.lax.Precision.HIGHEST
    earlier = jnp.tril(jnp.ones((n, n), bool), -1)[:, :, None]
    bonus = u.astype(f32)[:, None, :]

    def step(s, xs):
        rb, kb, vb, lb = (x.astype(f32) for x in xs)
        cum = jnp.cumsum(lb, axis=2)        # decay through each position
        before = cum - lb                   # ... up to just before it
        y = jnp.einsum("bhtk,bhkv->bhtv", rb * jnp.exp(before), s,
                       precision=hi)
        gap = before[:, :, :, None] - cum[:, :, None]
        att = jnp.einsum("bhtk,bhsk,bhtsk->bhts", rb, kb,
                         jnp.exp(jnp.where(earlier, gap, -jnp.inf)),
                         precision=hi)
        y = y + jnp.einsum("bhts,bhsv->bhtv", att, vb, precision=hi)
        y = y + (rb * bonus * kb).sum(-1, keepdims=True) * vb
        end = cum[:, :, -1:]
        s = jnp.exp(end[:, :, 0, :, None]) * s + jnp.einsum(
            "bhsk,bhsv->bhkv", kb * jnp.exp(end - cum), vb, precision=hi)
        return s, y

    s, ys = jax.lax.scan(step, s0.astype(f32),
                         tuple(map(blocks, (r, k, v, lw))))
    return ys.transpose(1, 0, 3, 2, 4).reshape(b, t, h, hd), s


def _last_valid(x: jax.Array, valid: jax.Array,
                x_last: jax.Array) -> jax.Array:
    """Each row's ``x`` (B,T,D) at its last valid position; ``x_last``
    (B,D) for a row with none."""
    n = valid.sum(axis=1)
    at = jnp.take_along_axis(x, jnp.maximum(n - 1, 0)[:, None, None],
                             axis=1)[:, 0]
    return jnp.where((n > 0)[:, None], at, x_last)


def wkv6_step(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
    u: jax.Array, s: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One token of the recurrence: r,k,v,w (B,H,hd); u (H,hd); s
    (B,H,hd,hd) float32 → (y (B,H,hd) float32, next state)."""
    r, k, v, w = (x.astype(jnp.float32) for x in (r, k, v, w))
    kv = k[..., :, None] * v[..., None, :]
    y = jnp.einsum("bhk,bhkv->bhv", r,
                   s + u.astype(jnp.float32)[..., None] * kv)
    return y, w[..., None] * s + kv


def decay(p, x_w: jax.Array) -> jax.Array:
    """Per-channel decay in (0, 1), float32:
    ``exp(-exp(time_decay + tanh(x_w D1) D2))``."""
    lora = jnp.tanh(x_w.astype(jnp.float32) @ p["wa"].astype(jnp.float32))
    w_log = p["w_base"].astype(jnp.float32) + jnp.einsum(
        "bsr,rhk->bshk", lora, p["wb"].astype(jnp.float32))
    return jnp.exp(-jnp.exp(w_log))


def _ddlerp(p, x, sx, dt):
    """Data-dependent lerp producing the five mixed inputs (w,k,v,r,g).

    Computed per-name (not as one stacked (5,B,T,D) einsum): the stacked form
    made the backward materialize 5×(B·T,D) f32 cotangents at once (~15 GB on
    the rwkv6-3b train_4k dry-run).
    """
    xxx = x + sx * p["mu_x"].astype(dt)
    out = {}
    for i, name in enumerate(MIX_NAMES):
        lora = (jnp.tanh(xxx @ p["lora_a"][i].astype(dt))
                @ p["lora_b"][i].astype(dt))
        out[name] = x + sx * (p["mu"][i].astype(dt) + lora)
    return out


def time_mix(
    p, x, cfg: ArchConfig, rules: ShardingRules,
    state: Optional[Dict[str, jax.Array]] = None,
    valid: Optional[jax.Array] = None,
):
    """state: {'s': (B,H,hd,hd), 'x_tm': (B,D)} for decode; None for train.

    With a ``valid`` (B,T) mask (and a state), the positions where it is
    False are padding: :func:`wkv6_masked` runs, and the carried ``x_tm``
    is each row's input at its last valid position.  Otherwise one token
    with a state runs :func:`wkv6_step`; anything longer the chunked
    :func:`wkv6`.
    """
    dt = cfg.compute_dtype
    b, t, d = x.shape
    h = p["u"].shape[0]
    x_last = None if state is None else state["x_tm"]
    sx = _shift(x, x_last) - x
    mixed = _ddlerp(p, x, sx, dt)

    def proj(name):
        return jnp.einsum("bsd,dhk->bshk", mixed[name], p["w" + name].astype(dt))

    r, k, v = proj("r"), proj("k"), proj("v")
    g = jax.nn.silu(proj("g"))
    for arr in (r, k, v):
        constrain(arr, rules, ("act_batch", "seq", "heads", "head_dim"))
    w = decay(p, mixed["w"])

    s0 = (
        jnp.zeros((b, h, HEAD_DIM, HEAD_DIM), jnp.float32)
        if state is None
        else state["s"]
    )
    if valid is not None:
        y, s_new = wkv6_masked(r, k, v, w, p["u"], s0, valid)
    elif state is not None and t == 1:
        y, s_new = wkv6_step(r[:, 0], k[:, 0], v[:, 0], w[:, 0], p["u"], s0)
        y = y[:, None]
    else:
        y, s_new = wkv6(r, k, v, w, p["u"], s0)

    # GroupNorm over the heads, float32.
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.var(y, axis=-1, keepdims=True)
    y = (y - mu) * jax.lax.rsqrt(var + GN_EPS)
    y = y * p["ln_x"]["w"].astype(jnp.float32) + p["ln_x"]["b"].astype(
        jnp.float32)
    y = y.astype(dt) * g
    out = jnp.einsum("bshk,hkd->bsd", y, p["wo"].astype(dt))
    x_tm = x[:, -1] if valid is None else _last_valid(x, valid, x_last)
    new_state = {"s": s_new, "x_tm": x_tm}
    return constrain(out, rules, ("act_batch", "seq", "d_model")), new_state


def channel_mix(
    p, x, cfg: ArchConfig, rules: ShardingRules,
    state: Optional[Dict[str, jax.Array]] = None,
    valid: Optional[jax.Array] = None,
):
    """``valid`` (B,T), as in :func:`time_mix`: the carried ``x_cm`` is each
    row's input at its last valid position."""
    dt = cfg.compute_dtype
    x_last = None if state is None else state["x_cm"]
    sx = _shift(x, x_last) - x
    xk = x + sx * p["mu_k"].astype(dt)
    xr = x + sx * p["mu_r"].astype(dt)
    k = jnp.square(jax.nn.relu(xk @ p["wk"].astype(dt)))
    k = constrain(k, rules, ("act_batch", "seq", "d_ff"))
    v = k @ p["wv"].astype(dt)
    r = jax.nn.sigmoid(xr @ p["wr"].astype(dt))
    out = r * v
    x_cm = x[:, -1] if valid is None else _last_valid(x, valid, x_last)
    return (
        constrain(out, rules, ("act_batch", "seq", "d_model")),
        {"x_cm": x_cm},
    )


def rwkv_block(
    p, x, cfg: ArchConfig, rules: ShardingRules,
    state: Optional[Dict[str, jax.Array]] = None,
    valid: Optional[jax.Array] = None,
):
    """One RWKV layer: x + TM(ln1(x)); x + CM(ln2(x)). Returns (x, state).

    ``valid`` (B,T) bool, with a state, marks the positions that move it
    (:func:`time_mix`); each row's are a prefix of the chunk.
    """
    tm_out, st_tm = time_mix(p["tm"], _ln(x, p["ln1"]), cfg, rules, state,
                             valid)
    x = x + tm_out
    cm_out, st_cm = channel_mix(p["cm"], _ln(x, p["ln2"]), cfg, rules, state,
                                valid)
    x = x + cm_out
    return x, {**st_tm, **st_cm}


def init_rwkv_state(
    cfg: ArchConfig, batch: int, heads: Optional[int] = None
) -> Dict[str, jax.Array]:
    d = cfg.d_model
    h = heads or rwkv_heads(cfg)
    return {
        "s": jnp.zeros((batch, h, HEAD_DIM, HEAD_DIM), jnp.float32),
        "x_tm": jnp.zeros((batch, d), cfg.compute_dtype),
        "x_cm": jnp.zeros((batch, d), cfg.compute_dtype),
    }


def rwkv_state_dims(cfg: ArchConfig):
    return {
        "s": ("cache_batch", "heads", None, None),
        "x_tm": ("cache_batch", None),
        "x_cm": ("cache_batch", None),
    }
