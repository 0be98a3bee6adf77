"""Plain reference of the attention-only stand-in, and its weights.

The stand-in is one tied embedding and ``num_hidden_layers`` attention
layers with a residual, and nothing else:

    x_0     = E[t]                                   (E: vocab x hidden)
    q, k, v = x W_q, x W_k, x W_v                    (per layer)
    a       = softmax(q k^T / sqrt(head_dim), causal) v   (GQA: query head j
              reads key/value head j // (heads / kv_heads))
    x       = x + a W_o
    logits  = x E^T

Departures from Yi-6B as published: no RMSNorm, no SwiGLU MLP, no rotary
position embedding, and the head is tied to the embedding.  Keys and values
are rounded to the configuration's ``kv_dtype`` (the page pool's), as the
configuration states; everything else is float32 at ``HIGHEST`` precision.
``control=True`` rounds the inputs of every projection (the four per-layer
weights, the head, and the activations they multiply) to float8 e4m3, the
precision below the bfloat16 products the program computes.

This module imports nothing of the program.  :func:`init_weights` makes the
weights from the seed; the benchmark hands the same arrays to the program.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: Weight statistics.  Without them a random tied-embedding model greedily
#: repeats its input token (x stays close to E[t], whose own logit wins by
#: a wide margin), and a comparison of served tokens could see nothing.
#: Layer 0's output projection is scaled up so that attention, not the
#: input token, decides the logits; every layer's W_q and W_k are scaled so
#: that its scores have a fixed spread on a probe sequence, so that no layer
#: saturates its softmax.
EMBED_STD = 0.02
OUT_GAIN_FIRST = 400.0
SCORE_SPREAD = 0.5
PROBE_TOKENS = 256


def dims(cfg: Dict) -> Dict[str, int]:
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "h": cfg["num_attention_heads"], "kvh": cfg["num_key_value_heads"],
            "hd": cfg["head_dim"], "vocab": cfg["vocab_size"]}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed below 2**64."""
    seed = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def _mm(a, b, control):
    if control:
        f8 = jnp.float8_e4m3fn
        a = a.astype(f8).astype(jnp.float32)
        b = b.astype(f8).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def _layer(w, l, x, g, kv_dtype, control, block):
    """One attention layer over a whole causal sequence x (T, d)."""
    t = x.shape[0]
    h, kvh, hd = g["h"], g["kvh"], g["hd"]
    q = _mm(x, w["wq"][l], control).reshape(t, kvh, h // kvh, hd)
    k = _mm(x, w["wk"][l], control).astype(kv_dtype).astype(jnp.float32)
    v = _mm(x, w["wv"][l], control).astype(kv_dtype).astype(jnp.float32)
    k = k.reshape(t, kvh, hd)
    v = v.reshape(t, kvh, hd)
    pos = jnp.arange(t)

    def attend(i):
        q_blk = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = jnp.einsum("qgrd,kgd->grqk", q_blk, k, precision=HIGHEST)
        s = s / np.sqrt(hd)
        qpos = i * block + jnp.arange(block)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HIGHEST)
        return o.reshape(block, h * hd)

    a = jax.lax.map(attend, jnp.arange(t // block)).reshape(t, h * hd)
    return x + _mm(a, w["wo"][l], control)


def init_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """Float32 weights drawn on the device in one jitted call (see the
    statistics above; W_v ~ N(0, 1/fan_in))."""
    g = dims(cfg)
    kv_dtype = jnp.dtype(cfg["kv_dtype"]).type

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 6)

        def proj(k, fan_in, fan_out):
            return (jax.random.normal(k, (g["layers"], fan_in, fan_out),
                                      jnp.float32) / np.sqrt(fan_in))

        gain = jnp.ones((g["layers"], 1, 1)).at[0].set(OUT_GAIN_FIRST)
        w = {
            "embed": jax.random.normal(ks[0], (g["vocab"], g["d"]),
                                       jnp.float32) * EMBED_STD,
            "wq": proj(ks[1], g["d"], g["h"] * g["hd"]),
            "wk": proj(ks[2], g["d"], g["kvh"] * g["hd"]),
            "wv": proj(ks[3], g["d"], g["kvh"] * g["hd"]),
            "wo": proj(ks[4], g["h"] * g["hd"], g["d"]) * gain,
        }
        probe = jax.random.randint(ks[5], (PROBE_TOKENS,), 0, g["vocab"])
        x = w["embed"][probe]
        for l in range(g["layers"]):
            s = np.sqrt(SCORE_SPREAD) / jnp.sqrt(jnp.mean(x * x))
            w["wq"] = w["wq"].at[l].multiply(s)
            w["wk"] = w["wk"].at[l].multiply(s)
            x = _layer(w, l, x, g, kv_dtype, False, PROBE_TOKENS)
        return w

    return make(seed_key(seed))


@functools.partial(jax.jit, static_argnames=("g", "kv_dtype", "control"))
def _logits(w, tokens, rows, *, g, kv_dtype, control):
    x = w["embed"][tokens]
    for l in range(g["layers"]):
        x = _layer(w, l, x, g, kv_dtype, control, 256)
    return _mm(x[rows], w["embed"].T, control)


def logits(weights: Dict[str, jax.Array], cfg: Dict, tokens: np.ndarray,
           rows: np.ndarray, pad_to: int, pad_rows: int,
           control: bool = False) -> np.ndarray:
    """Logits (len(rows), vocab) at positions ``rows`` of ``tokens``.

    ``tokens`` is zero-padded at its end to ``pad_to`` (a multiple of 256)
    and ``rows`` to ``pad_rows`` entries, so every sequence of a run shares
    one compiled program; padding after a position never reaches it under
    the causal mask.
    """
    buf = np.zeros((pad_to,), np.int32)
    buf[:len(tokens)] = tokens
    idx = np.full((pad_rows,), rows[-1], np.int32)
    idx[:len(rows)] = rows
    out = _logits(weights, jnp.asarray(buf), jnp.asarray(idx),
                  g=_Dims(dims(cfg)),
                  kv_dtype=jnp.dtype(cfg["kv_dtype"]).type, control=control)
    return np.asarray(out)[:len(rows)]


class _Dims(dict):
    """Hashable dims, for the jit's static arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
