"""Plain reference of RWKV-6 "Finch", and its weights.

The model of RWKV/v6-Finch-3B-HF (arXiv:2404.05892), written out from the
paper and RWKV-LM's v6 code, token by token:

    x_0 = LN_0(E[t])
    per layer:
      a      = LN_1(x);  sx = a_{t-1} - a              (a_{-1} = 0)
      m_i    = a + sx (maa_i + tanh((a + sx maa_x) W1)_i W2_i)
               for i in w, k, v, r, g                  (rank-32 LoRA)
      r, k, v = m_r R, m_k K, m_v V;  g = silu(m_g G)  (heads of 64)
      w      = exp(-exp(decay + tanh(m_w D1) D2))      (rank-64 LoRA)
      y_t    = r_t (S + diag(u) k_t v_t^T);  S <- diag(w_t) S + k_t v_t^T
      x      = x + (GroupNorm_heads(y) * g) O          (eps 1e-5 * 8^2)
      c      = LN_2(x);  sc = c_{t-1} - c
      x      = x + sigmoid((c + sc cmaa_r) CR) * (relu((c + sc cmaa_k) CK)^2 CV)
    logits = LN_out(x) H

Everything is float32 at ``HIGHEST`` precision, the recurrence one token
at a time over a float32 state.  ``control=True`` rounds the inputs of
every matrix product (weights and the activations they multiply) to float8
e4m3, the precision below the bfloat16 products the program computes.

This module imports nothing of the program.  :func:`init_weights` makes the
weights from the seed (rounded to the configuration's ``param_dtype`` and
held in float32); the benchmark hands the same numbers to the program.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MIX = ("w", "k", "v", "r", "g")

#: Weight statistics.  The mix and decay vectors follow RWKV-LM's v6
#: initialisation (decays spread over (0, 1) per channel, from fast to
#: slow); the LoRA factors, which that code starts at (near) zero, are drawn
#: so that each moves its lerp or log-decay by about ``MIX_LORA`` or
#: ``DECAY_LORA``, and the norms' weights and biases so that they differ
#: from 1 and 0.  Projections are N(0, 1/fan_in); the head gives logits of
#: unit scale.
MIX_LORA = 0.3
DECAY_LORA = 0.5
NORM_SPREAD = 0.1
#: Sequences per reference program (rows of one batched call).
BATCH = 8


def dims(cfg: Dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    hd = cfg["head_size"]
    return {"d": d, "layers": cfg["num_hidden_layers"], "h": d // hd,
            "hd": hd, "f": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"], "mix_r": cfg["time_mix_extra_dim"],
            "decay_r": cfg["time_decay_extra_dim"]}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed below 2**64."""
    seed = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def _layer_vectors(g: Dict[str, int], layer: int) -> Dict[str, np.ndarray]:
    """RWKV-LM v6's initial mix and decay vectors for one layer."""
    d, n_layers = g["d"], g["layers"]
    r01 = layer / max(n_layers - 1, 1)          # 0 to 1
    r10 = 1.0 - layer / n_layers                # 1 to almost 0
    ddd = np.arange(d) / d
    n = np.arange(d) / max(d - 1, 1)
    return {
        "maa_x": 1.0 - ddd ** r10,
        "maa": np.stack([1.0 - ddd ** r10,                  # w
                         1.0 - ddd ** r10,                  # k
                         1.0 - (ddd ** r10 + 0.3 * r01),    # v
                         1.0 - ddd ** (0.5 * r10),          # r
                         1.0 - ddd ** (0.5 * r10)]),        # g
        "decay": -6.0 + 5.0 * n ** (0.7 + 1.3 * r01),
        "faaaa": r01 * (1.0 - n) + ((np.arange(d) + 1) % 3 - 1) * 0.1,
        "cmaa_k": 1.0 - ddd ** r10,
        "cmaa_r": 1.0 - ddd ** r10,
    }


def init_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """The weights, float32 arrays holding ``param_dtype`` values: layer
    tensors stacked on a leading layer axis (statistics above)."""
    return weights_from_key(cfg, seed_key(seed))


def weights_from_key(cfg: Dict, key: jax.Array) -> Dict[str, jax.Array]:
    """:func:`init_weights` from the seed's PRNG key (:func:`seed_key`), so
    that a caller tracing it compiles one program for every seed."""
    g = dims(cfg)
    d, f, v, lay = g["d"], g["f"], g["vocab"], g["layers"]
    mr, dr = g["mix_r"], g["decay_r"]
    vec = [_layer_vectors(g, i) for i in range(lay)]
    vectors = {k: jnp.asarray(np.stack([x[k] for x in vec]), jnp.float32)
               for k in vec[0]}
    store = jnp.dtype(cfg["param_dtype"])

    @jax.jit
    def make(key, vectors):
        ks = iter(jax.random.split(key, 32))

        def normal(shape, std):
            return jax.random.normal(next(ks), shape, jnp.float32) * std

        def proj(fan_in, fan_out):
            return normal((lay, fan_in, fan_out), 1.0 / np.sqrt(fan_in))

        def norm(shape):
            return 1.0 + normal(shape, NORM_SPREAD), normal(shape, NORM_SPREAD)

        w = dict(vectors)
        w["emb"] = normal((v, d), 1.0)
        w["head"] = normal((d, v), 1.0 / np.sqrt(d))
        for name in ("ln0", "ln_out"):
            w[name + "_w"], w[name + "_b"] = norm((d,))
        for name in ("ln1", "ln2", "ln_x"):
            w[name + "_w"], w[name + "_b"] = norm((lay, d))
        w["maa_w1"] = proj(d, 5 * mr)
        w["maa_w2"] = normal((lay, 5, mr, d), MIX_LORA / np.sqrt(mr))
        w["decay_w1"] = proj(d, dr)
        w["decay_w2"] = normal((lay, dr, d), DECAY_LORA / np.sqrt(dr))
        for name in ("receptance", "key", "value", "gate", "output",
                     "cm_receptance"):
            w[name] = proj(d, d)
        w["cm_key"] = proj(d, f)
        w["cm_value"] = proj(f, d)
        return {k: a.astype(store).astype(jnp.float32) for k, a in w.items()}

    return make(key, vectors)


def _mm(a, b, control):
    if control:
        f8 = jnp.float8_e4m3fn
        a = a.astype(f8).astype(jnp.float32)
        b = b.astype(f8).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _shift(x):
    """x_{t-1} along axis 1, zeros at t = 0."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _wkv(r, k, v, w, u):
    """Token by token over axis 1: r, k, v, w (N, T, H, hd); u (H, hd)."""
    n, _, h, hd = r.shape

    def step(s, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("nhk,nhkv->nhv", rt, s + u[..., None] * kv,
                       precision=HIGHEST)
        return wt[..., None] * s + kv, y

    s0 = jnp.zeros((n, h, hd, hd), jnp.float32)
    _, ys = jax.lax.scan(step, s0, tuple(jnp.swapaxes(a, 0, 1)
                                         for a in (r, k, v, w)))
    return jnp.swapaxes(ys, 0, 1)


def _layer(w, l, x, g, eps, control):
    """One layer over sequences x (N, T, d)."""
    n, t, d = x.shape
    h, hd, mr = g["h"], g["hd"], g["mix_r"]
    a = _ln(x, w["ln1_w"][l], w["ln1_b"][l], eps)
    sx = _shift(a) - a
    xxx = a + sx * w["maa_x"][l]
    lora = jnp.tanh(_mm(xxx, w["maa_w1"][l], control)).reshape(n, t, 5, mr)
    m = {}
    for i, name in enumerate(MIX):
        m[name] = a + sx * (w["maa"][l, i]
                            + _mm(lora[:, :, i], w["maa_w2"][l, i], control))
    heads = lambda y: y.reshape(n, t, h, hd)
    r = heads(_mm(m["r"], w["receptance"][l], control))
    k = heads(_mm(m["k"], w["key"][l], control))
    v = heads(_mm(m["v"], w["value"][l], control))
    gate = jax.nn.silu(_mm(m["g"], w["gate"][l], control))
    dec = w["decay"][l] + _mm(jnp.tanh(_mm(m["w"], w["decay_w1"][l], control)),
                              w["decay_w2"][l], control)
    y = _wkv(r, k, v, heads(jnp.exp(-jnp.exp(dec))),
             w["faaaa"][l].reshape(h, hd))
    y = _ln(y, w["ln_x_w"][l].reshape(h, hd), w["ln_x_b"][l].reshape(h, hd),
            eps * g["divisor"] ** 2).reshape(n, t, d)
    x = x + _mm(y * gate, w["output"][l], control)
    c = _ln(x, w["ln2_w"][l], w["ln2_b"][l], eps)
    sc = _shift(c) - c
    kk = jnp.square(jax.nn.relu(_mm(c + sc * w["cmaa_k"][l], w["cm_key"][l],
                                    control)))
    rr = jax.nn.sigmoid(_mm(c + sc * w["cmaa_r"][l], w["cm_receptance"][l],
                            control))
    return x + rr * _mm(kk, w["cm_value"][l], control)


@functools.partial(jax.jit, static_argnames=("g", "eps", "control"))
def _logits(w, tokens, rows, *, g, eps, control):
    """tokens (N, T), rows (N, R) → logits (N, R, vocab)."""
    x = _ln(w["emb"][tokens], w["ln0_w"], w["ln0_b"], eps)
    for l in range(g["layers"]):
        x = _layer(w, l, x, g, eps, control)
    x = jnp.take_along_axis(x, rows[..., None], axis=1)
    x = _ln(x, w["ln_out_w"], w["ln_out_b"], eps)
    return _mm(x, w["head"], control)


def logits(weights: Dict[str, jax.Array], cfg: Dict,
           seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
           pad_to: int, pad_rows: int,
           control: bool = False) -> List[np.ndarray]:
    """Logits (len(rows[i]), vocab) at positions ``rows[i]`` of each
    sequence ``seqs[i]``, computed ``BATCH`` sequences at a time.

    Sequences are zero-padded at their end to ``pad_to`` tokens and their
    rows to ``pad_rows`` entries, so every batch of a run shares one
    compiled program; padding after a position never reaches it.
    """
    g = _Dims(dims(cfg), divisor=cfg["head_size_divisor"])
    out: List[np.ndarray] = []
    for lo in range(0, len(seqs), BATCH):
        part = range(lo, min(lo + BATCH, len(seqs)))
        toks = np.zeros((BATCH, pad_to), np.int32)
        idx = np.zeros((BATCH, pad_rows), np.int32)
        for j, i in enumerate(part):
            toks[j, :len(seqs[i])] = seqs[i]
            idx[j] = rows[i][-1]
            idx[j, :len(rows[i])] = rows[i]
        lg = np.asarray(_logits(weights, jnp.asarray(toks), jnp.asarray(idx),
                                g=g, eps=float(cfg["layer_norm_epsilon"]),
                                control=control))
        out += [lg[j, :len(rows[i])] for j, i in enumerate(part)]
    return out


class _Dims(dict):
    """Hashable dims, for the jit's static arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
