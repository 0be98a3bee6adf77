"""RWKV-6 "Finch" served through ``RecurrentLM`` / ``RecurrentFamily``.

What the harness needs of a family, for one configuration file:

* :func:`build` binds the program's model to a pool of state slots, with
  the weights made from the seed by the reference module, renamed into the
  program's tree and cast to the configuration's ``param_dtype``.
* :func:`warm` runs every program shape the traffic can reach once,
  through the family's public calls, on rows that write nothing.
* :func:`reference` gives the plain reference's logits (or the control's).
* the work counts (:func:`token_flops`, :func:`head_flops`,
  :func:`decode_cost`, :func:`prefill_cost`), from the configuration's
  shapes and the tokens a call serves: the least work whatever computes
  it, never counted from scan steps, masked rows or padding.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import rwkv6 as program
from repro.serve import RecurrentLM

from bench.reference import rwkv6 as ref


def arch(cfg: Dict) -> ArchConfig:
    d = cfg["hidden_size"]
    return ArchConfig(
        name=cfg["name"], family="ssm", n_layers=cfg["num_hidden_layers"],
        d_model=d, n_heads=d // cfg["head_size"],
        n_kv_heads=d // cfg["head_size"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], ssm="rwkv6", tie_embeddings=False,
        dtype=cfg["dtype"], param_dtype=cfg["param_dtype"],
    )


def _check(cfg: Dict) -> None:
    """The program's fixed Finch constants are the configuration's."""
    want = {"head_size": program.HEAD_DIM,
            "head_size_divisor": program.HEAD_SIZE_DIVISOR,
            "layer_norm_epsilon": program.LN_EPS,
            "time_mix_extra_dim": program.LORA_R,
            "time_decay_extra_dim": program.DECAY_R,
            "attention_hidden_size": cfg["hidden_size"]}
    for key, value in want.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]} is not the program's {value}")


def params(cfg: Dict, w: Dict[str, jax.Array]) -> Dict:
    """The reference's weights as ``RecurrentLM``'s parameter tree."""
    d = cfg["hidden_size"]
    h, hd = d // cfg["head_size"], cfg["head_size"]
    lay, mr = cfg["num_hidden_layers"], cfg["time_mix_extra_dim"]
    dt = jnp.dtype(cfg["param_dtype"])

    def norm(name, shape=None):
        pw, pb = w[name + "_w"], w[name + "_b"]
        if shape is not None:
            pw, pb = pw.reshape(shape), pb.reshape(shape)
        return {"w": pw.astype(dt), "b": pb.astype(dt)}

    heads = lambda a: a.reshape(lay, d, h, hd)
    tm = {
        "mu_x": w["maa_x"], "mu": w["maa"],
        "lora_a": w["maa_w1"].reshape(lay, d, 5, mr).transpose(0, 2, 1, 3),
        "lora_b": w["maa_w2"],
        "w_base": w["decay"].reshape(lay, h, hd),
        "wa": w["decay_w1"],
        "wb": w["decay_w2"].reshape(lay, -1, h, hd),
        "u": w["faaaa"].reshape(lay, h, hd),
        "wr": heads(w["receptance"]), "wk": heads(w["key"]),
        "wv": heads(w["value"]), "wg": heads(w["gate"]),
        "wo": w["output"].reshape(lay, h, hd, d),
    }
    cm = {"mu_k": w["cmaa_k"], "mu_r": w["cmaa_r"], "wk": w["cm_key"],
          "wv": w["cm_value"], "wr": w["cm_receptance"]}
    layers = {"tm": {k: a.astype(dt) for k, a in tm.items()},
              "cm": {k: a.astype(dt) for k, a in cm.items()},
              "ln1": norm("ln1"), "ln2": norm("ln2")}
    layers["tm"]["ln_x"] = norm("ln_x", (lay, h, hd))
    return {"embed": w["emb"].astype(dt), "ln0": norm("ln0"),
            "ln_out": norm("ln_out"), "head": w["head"].astype(dt),
            "layers": layers}


def build(cfg: Dict, seed: int):
    """The scheduler-facing family over a fresh pool of ``slots``.

    The reference's float32 weights are made and cast inside one program,
    so that they are never all held beside the served copy; the seed's key
    is its argument, so every seed runs the same compiled program.
    """
    _check(cfg)
    weights = jax.jit(lambda key: params(cfg, ref.weights_from_key(cfg, key)))(
        ref.seed_key(seed))
    model = RecurrentLM(arch(cfg), None, params=weights)
    return model.bind(model.init_pool(cfg["slots"]))


def pow2_upto(n: int) -> List[int]:
    out, m = [], 1
    while m <= n:
        out.append(m)
        m *= 2
    return out


def plan(cfg: Dict, reach: Dict) -> Dict[str, list]:
    """Program shapes the traffic reaches.

    Decode launches chain power-of-two scans of at most the steps to the
    next boundary, a request's end (state slots never grow).  Prefill runs
    one scan program per chunk width, and a row gather per power-of-two
    row count up to the clients in flight.
    """
    rows = pow2_upto(min(reach["in_flight"], cfg["slots"]))
    if rows[-1] < min(reach["in_flight"], cfg["slots"]):
        rows.append(rows[-1] * 2)
    return {"decode": pow2_upto(max(reach["output"] - 1, 1)),
            "prefill": rows}


def warm(family, cfg: Dict, reach: Dict) -> int:
    """Run each reachable program once through the family's own calls, so
    that the jit caches the window uses hold it; returns how many.

    Every row is padding (``counts == 0``) or inactive, which writes
    nothing.
    """
    b, chunk = family.batch, cfg["prefill_chunk"]
    shapes = plan(cfg, reach)
    idle = np.zeros((b,), bool)
    feed = np.zeros((b,), np.int32)
    # A chain of n steps runs its largest power of two fed from the host
    # and the rest fed from the device: one chain of every size, plus each
    # size but the largest fed from the host, reaches both kinds of each.
    sizes = shapes["decode"]
    family.decode_steps(feed, idle, 2 * sizes[-1] - 1)
    for n in sizes[:-1]:
        family.decode_steps(feed, idle, n)
    for rows in shapes["prefill"]:
        z = np.zeros((rows,), np.int32)
        family.prefill_batch(np.zeros((rows, chunk), np.int32), z, z, z)
    return 2 * len(sizes) - 1 + 2 * len(shapes["prefill"])


def reference(cfg: Dict, seed: int, seqs: Sequence[np.ndarray],
              rows: Sequence[np.ndarray], pad_to: int, pad_rows: int,
              control: bool = False) -> List[np.ndarray]:
    """Reference (or control) logits at ``rows[i]`` of ``seqs[i]``, each
    sequence padded to ``pad_to`` tokens and ``pad_rows`` rows."""
    return ref.logits(ref.init_weights(cfg, seed), cfg, seqs, rows, pad_to,
                      pad_rows, control=control)


# -- work counts ---------------------------------------------------------


def _g(cfg: Dict):
    d = cfg["hidden_size"]
    return (d, cfg["num_hidden_layers"], d // cfg["head_size"],
            cfg["head_size"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["time_mix_extra_dim"], cfg["time_decay_extra_dim"])


def layer_params(cfg: Dict) -> int:
    """Parameters of one layer."""
    d, _, _, _, f, _, mr, dr = _g(cfg)
    time_mix = 5 * d * d + 2 * 5 * d * mr + 2 * d * dr + 10 * d
    channel_mix = 2 * d * f + d * d + 2 * d
    return time_mix + channel_mix + 4 * d


def _layer_flops(cfg: Dict) -> float:
    """FLOPs of one token through one layer: every matrix product, and the
    WKV update and read (six per state element)."""
    d, _, h, hd, f, _, mr, dr = _g(cfg)
    macs = 5 * d * d + 2 * 5 * d * mr + 2 * d * dr + 2 * d * f + d * d
    return 2.0 * macs + 6.0 * h * hd * hd


def token_flops(cfg: Dict, ctx: np.ndarray) -> float:
    """Model FLOPs of ``ctx.size`` tokens through every layer (the work of
    a token does not depend on its position; the head is counted apart)."""
    _, layers, *_ = _g(cfg)
    return float(np.asarray(ctx).size * layers * _layer_flops(cfg))


def head_flops(cfg: Dict) -> float:
    """FLOPs of one token's logits over the vocabulary."""
    d, *_, vocab, _, _ = _g(cfg)
    return 2.0 * d * vocab


def _bytes(cfg: Dict):
    """(bytes of the layers' weights, of the head's, of one slot's state in
    one layer, of one activation row)."""
    d, layers, h, hd, _, vocab, _, _ = _g(cfg)
    item = jnp.dtype(cfg["param_dtype"]).itemsize
    act = jnp.dtype(cfg["dtype"]).itemsize
    state = h * hd * hd * 4 + 2 * d * act
    return layers * layer_params(cfg) * item, d * vocab * item, state, d * act


def decode_cost(cfg: Dict, active: int):
    """(FLOPs, bytes) of one decode step of ``active`` rows: each row's
    token through every layer and the head; the weights read once, each
    row's state read and written in every layer, its embedding row."""
    _, layers, *_ = _g(cfg)
    weights, head, state, row = _bytes(cfg)
    flops = active * (token_flops(cfg, np.zeros(1)) + head_flops(cfg))
    byts = weights + head + active * (2 * layers * state + row)
    return flops, float(byts)


def prefill_cost(cfg: Dict, counts: np.ndarray, completing: int):
    """(FLOPs, bytes) of one prefill call whose real rows feed ``counts``
    tokens, ``completing`` of them ending their prompt: every token through
    every layer and the head for completing rows; the layers' weights read
    once, each row's state read and written once per layer, the tokens'
    embedding rows, and the head and the logits of completing rows."""
    _, layers, _, _, _, vocab, _, _ = _g(cfg)
    weights, head, state, row = _bytes(cfg)
    counts = np.asarray(counts)
    flops = token_flops(cfg, np.zeros(int(counts.sum()))) \
        + completing * head_flops(cfg)
    byts = (weights + counts.size * 2 * layers * state + counts.sum() * row
            + (head + completing * vocab * 4 if completing else 0))
    return flops, float(byts)
