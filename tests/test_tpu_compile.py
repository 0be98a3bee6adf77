"""Compile-only checks for one TPU v5e chip, with no chip attached.

The TPU compiler is installed even where no TPU is: these tests describe a
``v5e:2x2`` topology, lower the serving kernels with ``interpret=False`` at
the widths ``chip_smoke.py`` serves (yi-6b: 32 q / 4 kv heads of 128), and
the recurrent programs at the rwkv6-3b benchmark cell's, and compile them
for its first chip.  They catch what
interpret mode cannot — a block or DMA slice the tiling refuses, too much
SMEM or VMEM — at no chip time.  Nothing runs, so nothing here says
anything about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from bench.families import paged_attn as paged_bench
from bench.families import rwkv6 as rwkv_bench
from bench.trace import is_pallas, scalar_operands
from repro.configs import get_config
from repro.kernels import ops
from repro.kernels import stream_converters as sc
from repro.kernels.paged_decode import paged_decode_attention_kernel
from repro.kernels.paged_prefill import paged_prefill_attention_kernel
from repro.kernels.paged_verify import paged_verify_attention_kernel
from repro.serve import PagedKVCache, PagedLM, RecurrentLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, KVH, HD = 32, 4, 128
PAGE, BATCH, MAX_LEN = chip_smoke.PAGE, chip_smoke.BATCH, chip_smoke.MAX_LEN
N_PAGES = MAX_LEN // PAGE
POOL_PAGES = BATCH * N_PAGES
#: The benchmark's longctx cell: 64 slots of a 256-page table.
CELL_BATCH, CELL_PAGES = 64, 4096 // PAGE


@pytest.fixture(scope="module")
def topo():
    """The described chip; the module leaves the environment and the JAX
    config as it found them, for the tests that share its worker."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # The TPU compiler logs under /tmp unless told otherwise.
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without one; keep the cache out of it.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    """Shape → ShapeDtypeStruct placed on the described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the ops layer take its chip branch: it asks the default backend,
    which is the CPU here."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _on_chip(spec, tree):
    return jax.tree.map(lambda s: spec(s.shape, s.dtype), tree)


def _compile(fn, *args, donate_argnums=(), **kwargs):
    compiled = jax.jit(fn, donate_argnums=donate_argnums).lower(
        *args, **kwargs).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel lowered as interpreted HLO"
    return compiled


def _hlo_lines(compiled):
    """The compiled program's instructions, operands printed with their
    shapes as a profiler trace names them."""
    from jax._src.lib import _jax
    opts = _jax.HloPrintOptions()
    opts.print_operand_shape = True
    module = compiled.runtime_executable().hlo_modules()[0]
    return module.to_string(opts).splitlines()


# ---------------------------------------------------------------------------
# The four stream converters
# ---------------------------------------------------------------------------

CONVERTER_ROWS = [(jnp.bfloat16, 512), (jnp.int8, 512), (jnp.float32, 4)]


@pytest.mark.parametrize("dtype,width", CONVERTER_ROWS,
                         ids=["bf16x512", "int8x512", "f32x4"])
@pytest.mark.parametrize("op", ["strided_gather", "strided_scatter",
                                "indirect_gather", "indirect_scatter"])
def test_converter_compiles(spec, op, dtype, width):
    n, count = POOL_PAGES * PAGE, 20
    table = spec((n, width), dtype)
    rows = spec((count, width), dtype)
    idx = spec((count,), jnp.int32)
    if op == "strided_gather":
        _compile(lambda s: sc.strided_gather_kernel(s, 3, BATCH, count), table)
    elif op == "strided_scatter":
        _compile(lambda d, p: sc.strided_scatter_kernel(d, p, 3, BATCH),
                 table, rows)
    elif op == "indirect_gather":
        _compile(sc.indirect_gather_kernel, table, idx)
    else:
        _compile(sc.indirect_scatter_kernel, table, rows, idx)


# ---------------------------------------------------------------------------
# The paged attention kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "prefill", "verify",
                                    "decode_longctx"])
def test_paged_attention_compiles(spec, kernel, kv):
    """Each kernel at ``chip_smoke``'s shapes, and decode at the longctx
    cell's too (64 rows of a 256-page table).  The decode kernel keeps the
    signature ``bench/metrics/paged_decode_roofline.py`` finds it by: one
    Pallas call named ``paged_decode`` whose operands open with three
    ``s32`` scalars, aliasing none."""
    kv_dtype = jnp.int8 if kv == "int8" else jnp.bfloat16
    batch, n_pages = ((CELL_BATCH, CELL_PAGES) if kernel == "decode_longctx"
                      else (BATCH, N_PAGES))
    pages = spec((batch * n_pages, PAGE, KVH, HD), kv_dtype)
    scales = ([spec((batch * n_pages, PAGE, KVH), jnp.float32)] * 2
              if kv == "int8" else [])
    table = spec((batch, n_pages), jnp.int32)
    lens = spec((batch,), jnp.int32)
    if kernel.startswith("decode"):
        q = spec((batch, H, HD), jnp.float32)
        compiled = _compile(paged_decode_attention_kernel, q, pages, pages,
                            table, lens, *scales)
        calls = [ln for ln in _hlo_lines(compiled)
                 if "paged_decode" in ln.split("=", 1)[0]
                 and "custom-call(" in ln]
        assert len(calls) == 1
        assert is_pallas(calls[0]) and scalar_operands(calls[0]) == 3
        assert "output_to_operand_aliasing" not in calls[0]
        return
    c = chip_smoke.CHUNK if kernel == "prefill" else chip_smoke.SPEC_K
    q = spec((BATCH, c, H, HD), jnp.float32)
    kern = (paged_prefill_attention_kernel if kernel == "prefill"
            else paged_verify_attention_kernel)
    # starts/lengths and counts share one shape; pass the same spec twice.
    _compile(kern, q, pages, pages, table, lens, lens, *scales)


@pytest.mark.parametrize("op,launches", [("append", 2), ("chunk", 6)])
def test_int8_kv_writes_compile(spec, on_tpu, op, launches):
    """Quantize-on-write: the int8 codes go through the converters (one
    launch per pool for an append; gather, scatter, write-back for a chunk)
    and the fp32 scales through XLA's in-place scatter — no converter
    launch, and so no lane-padded copy of the scale pools."""
    pages = spec((POOL_PAGES, PAGE, KVH, HD), jnp.int8)
    scales = spec((POOL_PAGES, PAGE, KVH), jnp.float32)
    table = spec((BATCH, N_PAGES), jnp.int32)
    lens = spec((BATCH,), jnp.int32)
    if op == "append":
        new = spec((BATCH, KVH, HD), jnp.float32)

        def write(kp, vp, ks, vs, kn, vn, table, lens):
            return ops.paged_kv_append(kp, vp, kn, vn, table, lens,
                                       k_scale=ks, v_scale=vs)
    else:
        new = spec((BATCH, chip_smoke.CHUNK, KVH, HD), jnp.float32)

        def write(kp, vp, ks, vs, kn, vn, table, lens):
            return ops.paged_kv_write_chunk(kp, vp, kn, vn, table, lens, lens,
                                            k_scale=ks, v_scale=vs)
    compiled = _compile(write, pages, pages, scales, scales, new, new, table,
                        lens, donate_argnums=(0, 1, 2, 3))
    assert compiled.as_text().count("tpu_custom_call") == launches


# ---------------------------------------------------------------------------
# The recurrent programs at the rwkv6-3b cell's widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_recurrent_programs_take_the_state_pool_in_place(spec, program):
    """The benchmark's 4-layer rwkv6-3b model over 128 slots, chunk 64: each
    program compiles for the chip, and no instruction copies a whole state
    pool (the prefill gathers its rows and scatters them back in place; a
    new row's state is zeroed as it is gathered).  The prefill compiles at
    the cell's usual 4-row bucket and at the largest, 128 rows, where it
    must hold no per-token WKV state stack."""
    with open(os.path.join(ROOT, "bench", "configs", "rwkv6-3b-l4.json")) as f:
        cell = json.load(f)
    cfg = rwkv_bench.arch(cell)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: RecurrentLM(cfg, k).params, key)
    model = RecurrentLM(cfg, None, params=_on_chip(spec, params))
    batch, chunk = cell["slots"], cell["prefill_chunk"]
    pool = _on_chip(spec, jax.eval_shape(
        lambda: model.init_pool(batch).tensors))
    if program == "prefill":
        compiled = {}
        for rows in (4, batch):
            ids = spec((rows,), jnp.int32)
            compiled[rows] = model._prefill.lower(
                model.params, pool, spec((rows, chunk), jnp.int32), ids, ids,
                spec((rows,), jnp.bool_)).compile()
        mem = compiled[batch].memory_analysis()
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert used < 6e9, f"128-row prefill needs {used / 1e9:.2f} GB"
    else:
        compiled = {batch: model._decode.lower(
            model.params, pool, spec((batch,), jnp.int32),
            spec((batch,), jnp.bool_), 1).compile()}
    shapes = {f"{t.dtype.name}[{','.join(map(str, t.shape))}]"
              .replace("float32", "f32").replace("bfloat16", "bf16")
              for t in pool.values()}
    assert "f32[4,128,40,64,64]" in shapes
    for rows, c in compiled.items():
        copied = set(re.findall(
            r"= (\w+\[[\d,]*\])\{[^}]*\} copy(?:-start)?\(", c.as_text()))
        assert not copied & shapes, (rows, copied & shapes)


# ---------------------------------------------------------------------------
# One whole served program: PagedLM's fused decode at yi-6b widths
# ---------------------------------------------------------------------------


def test_paged_lm_fused_decode_program_compiles(spec, on_tpu):
    cfg = get_config("yi-6b")
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: PagedLM(cfg, k).params, key)
    model = PagedLM(cfg, key, params=_on_chip(spec, params))
    assert (model.h, model.kvh, model.hd) == (H, KVH, HD)

    def pools():
        c = PagedKVCache.create(cfg, batch=BATCH, max_len=MAX_LEN, page=PAGE)
        return c.k_pages, c.v_pages, c.page_table, c.lengths

    kp, vp, table, lens = _on_chip(spec, jax.eval_shape(pools))
    assert kp.dtype == jnp.bfloat16 and kp.shape[0] == cfg.n_layers
    tokens = spec((BATCH,), jnp.int32)
    active = spec((BATCH,), jnp.bool_)
    compiled = model._decode_many.lower(
        model.params, tokens, kp, vp, None, None, table, lens, active, n=4,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # The kernels read the pools where they lie: no copy or transpose of a
    # layer's pool, or of the stack, is made for them.
    pool = re.compile(r"[\[,]" + ",".join(map(str, kp.shape[1:])) + r"\]")
    for ln in _hlo_lines(compiled):
        m = re.search(r"= (\S+) ([\w-]+)\(", ln)
        assert not (m and pool.search(m.group(1))
                    and m.group(2) in ("copy", "transpose")), ln
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16e9, f"decode program needs {used / 1e9:.1f} GB"


# ---------------------------------------------------------------------------
# PagedLM's programs at the longctx cell's widths take the pools in place
# ---------------------------------------------------------------------------

#: Instructions that move no bytes: they name a buffer another one wrote.
PLUMBING = ("parameter", "bitcast", "get-tuple-element", "tuple", "while")


@pytest.mark.parametrize("program,size", [("decode", 1), ("decode", 4),
                                          ("prefill", 4), ("prefill", 16),
                                          ("verify", 2)],
                         ids=["decode-n1", "decode-n4", "prefill-r4",
                              "prefill-r16", "verify-n2"])
def test_paged_lm_programs_address_layers_inside_the_pool(spec, on_tpu,
                                                          program, size):
    """The benchmark's 4-layer yi-6b model over 64 slots of 256 pages (two
    1.07 GB bf16 pools): fused decode of 1 and 4 steps, prefill of 4 and
    16 rows at 256 context pages, and two fused speculative verify steps
    of 4 tokens.  Only the aliasing write kernels
    output an array the size of a layer's pool or more, tuples included —
    no slice of a layer out of a pool, no stack of the layers back — and
    the program's temporaries stay far below one pool."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "yi6b-attn-l4.json")) as f:
        cell = json.load(f)
    cfg = paged_bench.arch(cell)
    params = jax.eval_shape(lambda k: PagedLM(cfg, k).params,
                            jax.random.PRNGKey(0))
    model = PagedLM(cfg, None, params=_on_chip(spec, params),
                    kv_dtype=cell["kv_dtype"], spec_k=4)

    def pools():
        c = PagedKVCache.create(cfg, batch=cell["slots"],
                                max_len=cell["max_position_embeddings"],
                                page=cell["page_size"],
                                kv_dtype=model.kv_dtype)
        return c.k_pages, c.v_pages, c.page_table, c.lengths

    kp, vp, table, lens = _on_chip(spec, jax.eval_shape(pools))
    assert table.shape == (CELL_BATCH, CELL_PAGES)
    assert kp.dtype == jnp.bfloat16 and kp.shape[0] == 4
    if program == "decode":
        rows = spec((CELL_BATCH,), jnp.int32)
        compiled = model._decode_many.lower(
            model.params, rows, kp, vp, None, None, table, lens,
            spec((CELL_BATCH,), jnp.bool_), n=size).compile()
    elif program == "verify":
        rows = spec((CELL_BATCH,), jnp.int32)
        drafts = _on_chip(spec, jax.eval_shape(
            lambda: model.drafter.init_state(CELL_BATCH)))
        compiled = model._verify(4, CELL_PAGES).lower(
            model.params, rows, drafts, kp, vp, None, None, table, lens,
            spec((CELL_BATCH,), jnp.bool_), rows, n=size).compile()
    else:
        rows = spec((size,), jnp.int32)
        tokens = spec((size, cell["prefill_chunk"]), jnp.int32)
        compiled = model._prefill(cell["page_size"], CELL_PAGES).lower(
            model.params, tokens, rows, rows, rows, kp, vp, None, None,
            table, lens).compile()
    layer_pool = math.prod(kp.shape[1:])
    for ln in _hlo_lines(compiled):
        m = re.search(r"= (.+?) ([\w-]+)\(", ln)
        if not m or m.group(2) in PLUMBING:
            continue
        sizes = [math.prod(map(int, dims.split(",")))
                 for dims in re.findall(r"bf16\[([\d,]+)\]", m.group(1))]
        if max(sizes, default=0) >= layer_pool:
            assert (m.group(2) == "custom-call" and "tpu_custom_call" in ln
                    and "output_to_operand_aliasing" in ln), ln
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.5e9, f"{program} temporaries {temp / 1e9:.2f} GB"
