"""Top-level language-model assembly for all assigned architecture families.

One parameter-def tree + three entry points (``train_loss``, ``prefill``,
``decode_step``) cover every family; layers are stacked and scanned
(``lax.scan``) so HLO size and compile time are depth-independent — required
for the 64-layer/480B dry-runs on this host.  Per-layer heterogeneity
(gemma3 local/global, hymba global layers) is a traced flag consumed inside
the scanned block.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.parallel.sharding import ShardingRules, constrain
from .common import (
    Param,
    chunked_softmax_xent,
    init_params,
    map_params,
    rms_norm,
    stack_layer_defs,
)
from . import attention as attn_mod
from . import mlp as mlp_mod
from . import rwkv6 as rwkv_mod
from . import mamba as mamba_mod


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def layer_defs(cfg: ArchConfig, q_heads: int, kv_heads: int) -> Dict[str, Any]:
    d = cfg.d_model
    norms = {
        "ln1": Param((d,), ("d_model",), init="zeros"),
        "ln2": Param((d,), ("d_model",), init="zeros"),
    }
    if cfg.ssm == "rwkv6":
        return rwkv_mod.rwkv_defs(cfg, q_heads)  # its own LayerNorms
    if cfg.ssm == "hymba":
        return {
            "hymba": mamba_mod.hymba_defs(cfg, q_heads, kv_heads),
            "mlp": mlp_mod.mlp_defs(cfg),
            **norms,
        }
    block: Dict[str, Any] = {"attn": attn_mod.attention_defs(cfg, q_heads, kv_heads)}
    if cfg.n_experts:
        block["moe"] = mlp_mod.moe_defs(cfg)
    else:
        block["mlp"] = mlp_mod.mlp_defs(cfg)
    return {**block, **norms}


def model_defs(cfg: ArchConfig, tp: int = 1) -> Dict[str, Any]:
    q_heads, kv_heads = cfg.heads_for_tp(tp)
    if cfg.ssm == "rwkv6":
        q_heads = rwkv_mod.rwkv_heads(cfg, padded=tp > 1)
    vp = cfg.vocab_padded(tp)
    defs: Dict[str, Any] = {
        "layers": stack_layer_defs(layer_defs(cfg, q_heads, kv_heads), cfg.n_layers),
        "final_norm": Param((cfg.d_model,), ("d_model",), init="zeros"),
    }
    if cfg.modality != "audio":
        defs["embed"] = Param((vp, cfg.d_model), ("vocab", "d_model"), init="embed")
    if cfg.modality in ("audio", "vlm"):
        defs["frontend_proj"] = Param(
            (cfg.frontend_dim, cfg.d_model), ("frontend", "d_model")
        )
    if not cfg.tie_embeddings:
        defs["head"] = Param((cfg.d_model, vp), ("d_model", "vocab"))
    return defs


def init_model(cfg: ArchConfig, key: jax.Array, tp: int = 1):
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[cfg.param_dtype]
    return init_params(model_defs(cfg, tp), key, dtype)


MLP_WEIGHT_NAMES = ("w_up", "w_gate", "w_down")


def quantize_mlp_weights(params, cfg: ArchConfig):
    """w8a16: replace MLP/MoE weight leaves by {'q': int8, 'scale': f32}.

    Per-output-channel symmetric scales (axis=-2, the contraction dim, with
    keepdims so dequant broadcasts).  Serving-side narrow-element packing:
    halves resident weight bytes and the HBM stream per matmul — on
    qwen1.5-32b it removes the need for data-sharded MLP weights entirely
    (EXPERIMENTS.md §Perf A, iteration 4).
    """

    def walk(tree, in_mlp=False):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                if in_mlp and k in MLP_WEIGHT_NAMES and hasattr(v, "dtype"):
                    amax = jnp.max(jnp.abs(v.astype(jnp.float32)), axis=-2,
                                   keepdims=True)
                    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
                    q = jnp.clip(jnp.round(v.astype(jnp.float32) / scale),
                                 -127, 127).astype(jnp.int8)
                    out[k] = {"q": q, "scale": scale.astype(jnp.float32)}
                else:
                    out[k] = walk(v, in_mlp or k in ("mlp", "moe", "dense"))
            return out
        return tree

    return walk(params)


def quantize_mlp_structs(sds_tree, spec_tree, cfg: ArchConfig):
    """Abstract (ShapeDtypeStruct, sharding-spec) version for the dry-run."""
    import dataclasses as _dc

    def walk(sds, spec, in_mlp=False):
        if isinstance(sds, dict):
            o1, o2 = {}, {}
            for k in sds:
                if in_mlp and k in MLP_WEIGHT_NAMES and hasattr(sds[k], "shape"):
                    shp = sds[k].shape
                    sshp = shp[:-2] + (1,) + shp[-1:]
                    o1[k] = {
                        "q": jax.ShapeDtypeStruct(shp, jnp.int8),
                        "scale": jax.ShapeDtypeStruct(sshp, jnp.float32),
                    }
                    # the contracted (-2) dim collapses to 1 in the scale:
                    # drop its mesh axis from the spec
                    wspec = spec[k]
                    from jax.sharding import NamedSharding, PartitionSpec as P
                    ps = list(wspec.spec) + [None] * (len(shp) - len(wspec.spec))
                    ps[len(shp) - 2] = None
                    sspec = NamedSharding(wspec.mesh, P(*ps))
                    o2[k] = {"q": wspec, "scale": sspec}
                else:
                    r1, r2 = walk(sds[k], spec[k],
                                  in_mlp or k in ("mlp", "moe", "dense"))
                    o1[k], o2[k] = r1, r2
            return o1, o2
        return sds, spec

    return walk(sds_tree, spec_tree)


def global_flags(cfg: ArchConfig) -> np.ndarray:
    """Per-layer is-global-attention flags (float for traced select)."""
    l = cfg.n_layers
    if cfg.ssm == "hymba":
        flags = np.zeros(l)
        flags[[0, l // 2, l - 1]] = 1.0
        return flags
    if cfg.global_interval is None:
        return np.ones(l)
    return np.array([float(cfg.layer_is_global(i)) for i in range(l)])


# ---------------------------------------------------------------------------
# Blocks (one scanned step per family)
# ---------------------------------------------------------------------------


def _block_train(p, x, cfg, rules, is_global, positions):
    """Returns (x, aux_loss)."""
    if cfg.ssm == "rwkv6":
        x, _ = rwkv_mod.rwkv_block(p, x, cfg, rules)
        return x, jnp.float32(0.0)
    if cfg.ssm == "hymba":
        h = mamba_mod.hymba_block_fwd(
            p["hymba"], rms_norm(x, p["ln1"]), cfg, rules, is_global, positions
        )
        x = x + h
        x = x + mlp_mod.mlp_fwd(p["mlp"], rms_norm(x, p["ln2"]), cfg, rules)
        return x, jnp.float32(0.0)
    a = attn_mod.attention_fwd(
        p["attn"], rms_norm(x, p["ln1"]), cfg, rules, is_global, positions
    )
    x = x + a
    if cfg.n_experts:
        m, aux = mlp_mod.moe_fwd(p["moe"], rms_norm(x, p["ln2"]), cfg, rules)
    else:
        m, aux = mlp_mod.mlp_fwd(p["mlp"], rms_norm(x, p["ln2"]), cfg, rules), jnp.float32(0.0)
    return x + m, aux


def _block_prefill(p, x, cfg, rules, is_global, cache):
    if cfg.ssm == "rwkv6":
        x, st = rwkv_mod.rwkv_block(p, x, cfg, rules)
        # prefill leaves the final state in the cache
        return x, st
    if cfg.ssm == "hymba":
        h, cache = mamba_mod.hymba_block_prefill(
            p["hymba"], rms_norm(x, p["ln1"]), cfg, rules, is_global, cache
        )
        x = x + h
        x = x + mlp_mod.mlp_fwd(p["mlp"], rms_norm(x, p["ln2"]), cfg, rules)
        return x, cache
    a, cache = attn_mod.attention_prefill(
        p["attn"], rms_norm(x, p["ln1"]), cfg, rules, is_global, cache
    )
    x = x + a
    if cfg.n_experts:
        m, _ = mlp_mod.moe_fwd(p["moe"], rms_norm(x, p["ln2"]), cfg, rules)
    else:
        m = mlp_mod.mlp_fwd(p["mlp"], rms_norm(x, p["ln2"]), cfg, rules)
    return x + m, cache


def _block_decode(p, x, cfg, rules, is_global, cache, pos):
    if cfg.ssm == "rwkv6":
        return rwkv_mod.rwkv_block(p, x, cfg, rules, state=cache)
    if cfg.ssm == "hymba":
        h, cache = mamba_mod.hymba_block_decode(
            p["hymba"], rms_norm(x, p["ln1"]), cfg, rules, is_global, cache, pos
        )
        x = x + h
        x = x + mlp_mod.mlp_fwd(p["mlp"], rms_norm(x, p["ln2"]), cfg, rules)
        return x, cache
    a, cache = attn_mod.attention_decode(
        p["attn"], rms_norm(x, p["ln1"]), cfg, rules, is_global, cache, pos
    )
    x = x + a
    if cfg.n_experts:
        m, _ = mlp_mod.moe_fwd(p["moe"], rms_norm(x, p["ln2"]), cfg, rules)
    else:
        m = mlp_mod.mlp_fwd(p["mlp"], rms_norm(x, p["ln2"]), cfg, rules)
    return x + m, cache


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed_lookup(table, ids, rules: ShardingRules, dt):
    """Vocab-sharded embedding gather as an explicit packed indirect stream.

    Plain ``jnp.take`` on a vocab-sharded table makes the SPMD partitioner
    all-gather the whole table per device (observed: 671 MB f32 copies per
    step on rwkv6-3b).  The shard_map form keeps the gather *local* — each
    shard packs only its resident rows and a psum combines — the memory-side
    indirection move of the paper.

    The backward is explicit (custom_vjp): without it the partitioner
    all-gathers the (global_batch, S, D) cotangent to every device before
    the scatter-add (observed: a 10 GB f32 all-gather); the custom rule does
    a fully local scatter-add over (data×vocab) shards and psums only the
    table-shard gradient across 'data'.
    """
    ax = rules.axis("vocab")
    n = rules.axis_size("vocab")
    if rules.mesh is None or not isinstance(ax, str) or n == 1:
        return jnp.take(table, ids, axis=0).astype(dt)
    from jax.sharding import PartitionSpec as P

    vs = table.shape[0] // n
    mesh = rules.mesh
    batch_ax = rules.axis("batch")  # e.g. ('data',) or ('pod','data') or None
    if isinstance(batch_ax, str):
        batch_ax = (batch_ax,)

    # XLA:CPU's AllReducePromotion pass crashes on bf16 all-reduce (dry-run
    # host only); TPU does native bf16 psum.
    psum_dt = jnp.float32 if jax.default_backend() == "cpu" else dt

    def local_fwd(tbl, ids_):
        lo = jax.lax.axis_index(ax) * vs
        loc = ids_ - lo
        ok = (loc >= 0) & (loc < vs)
        x = jnp.take(tbl, jnp.clip(loc, 0, vs - 1), axis=0).astype(psum_dt)
        out = jax.lax.psum(jnp.where(ok[..., None], x, jnp.zeros((), psum_dt)), ax)
        return out.astype(dt)

    fwd_mapped = jax.shard_map(
        local_fwd, mesh=mesh, in_specs=(P(ax, None), P()), out_specs=P(),
        axis_names={ax}, check_vma=False,
    )

    manual_bwd = {ax, *(batch_ax or ())}
    ids_spec = P(batch_ax) if batch_ax else P()

    def local_bwd(ids_, g_):
        # ids_ (B_local, S); g_ (B_local, S, D) — all local, no gathers.
        lo = jax.lax.axis_index(ax) * vs
        loc = ids_ - lo
        ok = (loc >= 0) & (loc < vs)
        upd = jnp.where(ok[..., None], g_.astype(psum_dt), 0.0)
        gt = jnp.zeros((vs, g_.shape[-1]), psum_dt)
        gt = gt.at[jnp.clip(loc, 0, vs - 1).reshape(-1)].add(
            upd.reshape(-1, g_.shape[-1])
        )
        if batch_ax:
            gt = jax.lax.psum(gt, batch_ax)
        return gt

    bwd_mapped = jax.shard_map(
        local_bwd, mesh=mesh,
        in_specs=(ids_spec, ids_spec),  # trailing dims implicitly unsharded
        out_specs=P(ax, None),
        axis_names=manual_bwd, check_vma=False,
    )

    table_dtype = table.dtype  # static closure (not a vjp residual)

    @jax.custom_vjp
    def lookup(tbl, ids_):
        return fwd_mapped(tbl, ids_)

    def fwd_rule(tbl, ids_):
        return fwd_mapped(tbl, ids_), ids_

    def bwd_rule(ids_, g_):
        gt = bwd_mapped(ids_, g_)
        return gt.astype(table_dtype), None

    lookup.defvjp(fwd_rule, bwd_rule)
    return lookup(table, ids)


def embed_tokens(params, batch, cfg: ArchConfig, rules: ShardingRules):
    dt = cfg.compute_dtype
    parts = []
    if cfg.modality in ("audio", "vlm") and "frontend" in batch:
        fe = batch["frontend"].astype(dt) @ params["frontend_proj"].astype(dt)
        parts.append(fe)
    if cfg.modality != "audio":
        x = _embed_lookup(params["embed"], batch["tokens"], rules, dt)
        if cfg.name.startswith("gemma"):
            x = x * jnp.asarray(np.sqrt(cfg.d_model), dt)
        parts.append(x)
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return constrain(x, rules, ("act_batch", "seq", "d_model"))


def output_weight(params, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["head"]


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _diff_barrier(x):
    """``optimization_barrier`` with an explicit gradient rule.

    The jax pinned on this host (<0.5) has no differentiation rule for the
    barrier primitive; newer releases differentiate it as identity.  The
    custom rule barriers the cotangents too, so the backward loop keeps the
    same anti-hoisting property the forward barrier exists for.
    """
    return jax.lax.optimization_barrier(x)


def _diff_barrier_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _diff_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_diff_barrier.defvjp(_diff_barrier_fwd, _diff_barrier_bwd)


def _scan_layers(params, x, cfg, rules, body):
    flags = jnp.asarray(global_flags(cfg), jnp.float32)

    def step(carry, xs):
        lp, flag = xs
        # The barrier pins per-layer residual reads inside the backward loop:
        # without it XLA hoists the f32 upcast of the *entire* stacked
        # residual (L,B,S,D) out of the loop (observed: a 21 GB convert).
        carry = _diff_barrier(carry)
        return body(carry, lp, flag)

    if cfg.remat:
        step = jax.checkpoint(step, prevent_cse=False)
    return jax.lax.scan(step, x, (params["layers"], flags))


def train_loss(
    params, batch, cfg: ArchConfig, rules: ShardingRules
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: tokens (B,S), targets (B,S), mask (B,S) [+ frontend]."""
    x = embed_tokens(params, batch, cfg, rules)
    s = x.shape[1]
    positions = jnp.arange(s)

    def body(carry, lp, flag):
        x, aux = carry
        x, a = _block_train(lp, x, cfg, rules, flag, positions)
        return (x, aux + a), None

    (x, aux), _ = _scan_layers(params, (x, jnp.float32(0.0)), cfg, rules, body)
    x = rms_norm(x, params["final_norm"])
    w_out = output_weight(params, cfg).astype(cfg.compute_dtype)
    tgt = batch["targets"]
    # Align targets when a frontend prefix was prepended.
    if x.shape[1] != tgt.shape[1]:
        x = x[:, x.shape[1] - tgt.shape[1]:]
    loss, cnt = chunked_softmax_xent(
        x, w_out, tgt, batch.get("mask"), n_valid=cfg.vocab,
        logit_spec=rules.spec(("act_batch", None, "vocab")),
    )
    total = loss + cfg.router_aux_coef * aux / cfg.n_layers
    return total, {"ce_loss": loss, "aux_loss": aux, "tokens": cnt}


def init_cache(cfg: ArchConfig, batch: int, max_len: int, tp: int = 1):
    """Stacked per-layer cache pytree (leading dim = layers)."""
    q_heads, kv_heads = cfg.heads_for_tp(tp)
    if cfg.ssm == "rwkv6":
        one = rwkv_mod.init_rwkv_state(cfg, batch, rwkv_mod.rwkv_heads(cfg, tp > 1))
    elif cfg.ssm == "hymba":
        one = {
            "kv": attn_mod.init_kv_cache(cfg, q_heads, kv_heads, batch, max_len),
            "ssm": mamba_mod.init_mamba_state(cfg, batch),
        }
    else:
        one = attn_mod.init_kv_cache(cfg, q_heads, kv_heads, batch, max_len)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape), one
    )


def cache_dims_tree(cfg: ArchConfig):
    """Logical dims for every cache leaf (layers dim prepended)."""
    if cfg.ssm == "rwkv6":
        dims = rwkv_mod.rwkv_state_dims(cfg)
    elif cfg.ssm == "hymba":
        dims = {
            "kv": attn_mod.cache_dims(cfg),
            "ssm": mamba_mod.mamba_state_dims(cfg),
        }
    else:
        dims = attn_mod.cache_dims(cfg)
    return jax.tree_util.tree_map(
        lambda d: ("layers",) + d, dims, is_leaf=lambda d: isinstance(d, tuple)
    )


def _scan_with_cache(params, x, cache, cfg, rules, block_fn):
    """Scan layers with the full cache stack as a *carry*, updated in place
    per layer (dynamic_update_index).  Carrying (vs. emitting stacked ys)
    lets XLA alias the donated cache buffer through the loop — with the ys
    form the dry-run showed a full second cache in the temp arena."""
    flags = jnp.asarray(global_flags(cfg), jnp.float32)

    def step(carry, xs):
        x, cache_all = carry
        lp, flag, i = xs
        lcache = jax.tree_util.tree_map(
            lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False),
            cache_all,
        )
        x, new_l = block_fn(lp, x, flag, lcache)
        cache_all = jax.tree_util.tree_map(
            lambda c, nl: jax.lax.dynamic_update_index_in_dim(c, nl, i, 0),
            cache_all, new_l,
        )
        return (x, cache_all), None

    idx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, cache), _ = jax.lax.scan(step, (x, cache), (params["layers"], flags, idx))
    return x, cache


def prefill(params, batch, cache, cfg: ArchConfig, rules: ShardingRules):
    """Fill the cache from a prompt; returns (last-token logits, cache)."""
    x = embed_tokens(params, batch, cfg, rules)
    x, cache = _scan_with_cache(
        params, x, cache, cfg, rules,
        lambda lp, x_, flag, lc: _block_prefill(lp, x_, cfg, rules, flag, lc),
    )
    x = rms_norm(x[:, -1:], params["final_norm"])
    w_out = output_weight(params, cfg).astype(cfg.compute_dtype)
    return x @ w_out, cache


def decode_step(params, tokens, cache, pos, cfg: ArchConfig, rules: ShardingRules):
    """One decode step: tokens (B,1) at position ``pos`` → (logits, cache)."""
    x = embed_tokens(params, {"tokens": tokens}, cfg, rules)
    x, cache = _scan_with_cache(
        params, x, cache, cfg, rules,
        lambda lp, x_, flag, lc: _block_decode(lp, x_, cfg, rules, flag, lc, pos),
    )
    x = rms_norm(x, params["final_norm"])
    w_out = output_weight(params, cfg).astype(cfg.compute_dtype)
    return (x @ w_out)[:, 0], cache


def extend_step(params, tokens, cache, pos, cfg: ArchConfig, rules: ShardingRules):
    """Process a chunk of tokens (B,C) at positions [pos, pos+C) against the
    cache (chunked prefill / vLLM-style prompt processing).  The decode
    attention path is C-generic, so this is decode_step with C>1."""
    x = embed_tokens(params, {"tokens": tokens}, cfg, rules)
    x, cache = _scan_with_cache(
        params, x, cache, cfg, rules,
        lambda lp, x_, flag, lc: _block_decode(lp, x_, cfg, rules, flag, lc, pos),
    )
    x = rms_norm(x[:, -1:], params["final_norm"])
    w_out = output_weight(params, cfg).astype(cfg.compute_dtype)
    return x @ w_out, cache


def prefill_chunked(
    params, batch, cache, cfg: ArchConfig, rules: ShardingRules, chunk: int
):
    """Prefill in fixed-size chunks: activation and attention-score memory
    scale with ``chunk`` instead of the full prompt (arctic-480b prefill_32k:
    17.1 → see EXPERIMENTS §Dry-run).  Equivalent to ``prefill`` (asserted in
    tests); MoE capacity is per-chunk, matching continuous-batching serving."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    chunks = tokens.reshape(b, n, chunk).transpose(1, 0, 2)

    def step(cache, xs):
        tok, i = xs
        logits, cache = extend_step(params, tok, cache, i * chunk, cfg, rules)
        return cache, logits

    cache, logits = jax.lax.scan(
        step, cache, (chunks, jnp.arange(n, dtype=jnp.int32))
    )
    return logits[-1], cache
