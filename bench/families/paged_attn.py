"""The attention-only stand-in served through ``PagedLM`` / ``PagedFamily``.

What the harness needs of a family, for one configuration file:

* :func:`build` binds the program's model to its pool, with the weights
  made from the seed by the reference module (one jitted call).
* :func:`warm` runs every program shape the traffic can reach once,
  through the family's public calls, on rows that write nothing.
* :func:`reference` gives the plain reference's logits (or the control's).
* the work counts (:func:`token_flops`, :func:`decode_kernel`,
  :func:`prefill_kernel`), from the configuration's shapes and the tokens a
  call serves: never from pages, padding or grid steps.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.serve import PagedKVCache, PagedLM

from bench.reference import paged_attn as ref


def arch(cfg: Dict) -> ArchConfig:
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=0, vocab=cfg["vocab_size"],
    )


def build(cfg: Dict, seed: int):
    """The scheduler-facing family over fresh pools."""
    model = PagedLM(arch(cfg), None, params=ref.init_weights(cfg, seed),
                    kv_dtype=cfg["kv_dtype"])
    cache = PagedKVCache.create(
        model.cfg, batch=cfg["slots"], max_len=cfg["max_position_embeddings"],
        page=cfg["page_size"], kv_dtype=model.kv_dtype)
    return model.bind(cache)


def pow2_upto(n: int) -> List[int]:
    out, m = [], 1
    while m <= n:
        out.append(m)
        m *= 2
    return out


def plan(cfg: Dict, reach: Dict) -> Dict[str, list]:
    """Program shapes the traffic reaches.

    Decode launches chain power-of-two scans of at most the steps to the
    next boundary: a request's end, or its slot's next page (the scheduler
    maps pages one at a time), so never more than a page of tokens.  Prefill
    programs are keyed by rows (a power of two up to the clients in
    flight) and context pages (the power of two covering any chunk end of
    any prompt length in the mix).
    """
    page, chunk = cfg["page_size"], cfg["prefill_chunk"]
    n_pages = cfg["max_position_embeddings"] // page
    ctxs = set()
    for p in range(reach["min_prompt"], reach["prompt"] + 1):
        for start in range(0, p, chunk):
            need = -(-min(start + chunk, p) // page)
            ctxs.add(min(1 << (need - 1).bit_length(), n_pages))
    rows = pow2_upto(min(reach["in_flight"], cfg["slots"]))
    if rows[-1] < min(reach["in_flight"], cfg["slots"]):
        rows.append(rows[-1] * 2)
    return {"decode": pow2_upto(max(min(reach["output"] - 1, page), 1)),
            "prefill": [(c, r) for c in sorted(ctxs) for r in rows]}


def warm(family, cfg: Dict, reach: Dict) -> int:
    """Run each reachable program once through the family's own calls, so
    that the jit caches the window uses hold it; returns how many.

    Every row is padding (``counts == 0``) or inactive, which writes
    nothing and leaves every length at 0.
    """
    b, page, chunk = family.batch, cfg["page_size"], cfg["prefill_chunk"]
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
    for ctx, rows in shapes["prefill"]:
        z = np.zeros((rows,), np.int32)
        starts = np.full((rows,), ctx * page, np.int32)
        np.asarray(family.prefill_batch(np.zeros((rows, chunk), np.int32), z,
                                        z, starts))
    return 2 * len(sizes) - 1 + len(shapes["prefill"])


def reference(cfg: Dict, seed: int, seqs: Sequence[np.ndarray],
              rows: Sequence[np.ndarray], pad_to: int, pad_rows: int,
              control: bool = False) -> List[np.ndarray]:
    """Reference (or control) logits at ``rows[i]`` of ``seqs[i]``, each
    sequence padded to ``pad_to`` tokens and ``pad_rows`` rows."""
    w = ref.init_weights(cfg, seed)
    return [ref.logits(w, cfg, s, r, pad_to, pad_rows, control=control)
            for s, r in zip(seqs, rows)]


# -- work counts ---------------------------------------------------------


def _g(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"])


def token_flops(cfg: Dict, ctx: np.ndarray) -> float:
    """Model FLOPs of tokens that attend to ``ctx`` keys each (all layers,
    projections and attention; the head is counted apart)."""
    d, layers, h, kvh, hd, _ = _g(cfg)
    ctx = np.asarray(ctx, np.float64)
    proj = 2.0 * d * (2 * h * hd + 2 * kvh * hd)
    return float(layers * (proj * ctx.size + 4.0 * h * hd * ctx.sum()))


def head_flops(cfg: Dict) -> float:
    """FLOPs of one token's logits over the vocabulary."""
    d, _, _, _, _, vocab = _g(cfg)
    return 2.0 * d * vocab


def _kv_bytes(cfg: Dict) -> int:
    _, _, _, kvh, hd, _ = _g(cfg)
    return 2 * kvh * hd * jnp.dtype(cfg["kv_dtype"]).itemsize


def decode_kernel(cfg: Dict, lens: np.ndarray):
    """(FLOPs, bytes) of one layer's decode attention for rows whose live
    lengths (the new token included) are ``lens``: the K/V of every live
    token, plus each row's float32 query and output."""
    _, _, h, _, hd, _ = _g(cfg)
    lens = np.asarray(lens, np.float64)
    flops = 4.0 * h * hd * lens.sum()
    byts = _kv_bytes(cfg) * lens.sum() + 2.0 * 4 * h * hd * lens.size
    return flops, byts


def prefill_kernel(cfg: Dict, starts: np.ndarray, counts: np.ndarray):
    """(FLOPs, bytes) of one layer's causal chunk attention for rows that
    add ``counts`` tokens after ``starts``: each row's K/V up to
    ``start + count``, plus the chunk's float32 queries and outputs."""
    _, _, h, _, hd, _ = _g(cfg)
    s = np.asarray(starts, np.float64)
    c = np.asarray(counts, np.float64)
    keys = (c * s + c * (c + 1) / 2).sum()      # sum over queries of p + 1
    flops = 4.0 * h * hd * keys
    byts = _kv_bytes(cfg) * (s + c).sum() + 2.0 * 4 * h * hd * c.sum()
    return flops, byts
