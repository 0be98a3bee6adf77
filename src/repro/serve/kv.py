"""Paged KV pool: fixed-size physical pages + per-sequence page tables.

Split out of the old ``serve/engine.py``: this module owns only the pool
data structure and its host bookkeeping — the transformer engine that
serves from it lives in ``repro.serve.paged_lm``.

The page table is the AXI-Pack indirect stream descriptor: decode
attention resolves it on device (scalar-prefetched page ids → direct HBM
page DMAs) while the scheduler does all allocation/refcount bookkeeping
against the host shadows, never syncing device state on the hot path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from .family import OutOfPages

__all__ = ["OutOfPages", "PagedKVCache"]


@contextlib.contextmanager
def _donation_noop_ok():
    """Silence jax's donation-unusable warning for one library dispatch.

    Pool donation is a deliberate no-op on CPU backends and the fast path is
    identical either way, so the warning is noise *for these calls only* —
    the suppression is scoped with ``catch_warnings`` so user code's own
    donation diagnostics (where a failed donation is a real memory bug) are
    never swallowed."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_pool_page(pool: jax.Array, src, dst) -> jax.Array:
    """``pool[:, dst] = pool[:, src]`` across all layers, in place.

    ``src``/``dst`` are traced scalars, so every copy-on-write page copy
    reuses one compiled program per pool shape/dtype; donation lets XLA
    alias the update into the resident pool instead of cloning it.
    """
    return pool.at[:, dst].set(pool[:, src])


@dataclasses.dataclass
class PagedKVCache:
    """Physical page pool + per-sequence page tables (one per layer stack).

    The dataclass is *functional*: ``allocate``/``release`` copy every piece
    of host bookkeeping they touch before writing (``free``, ``mapped``,
    ``lengths_host``, ``page_table_host``, ``refcounts``) and return a new
    cache, so a retained older cache object is never corrupted by later
    calls.  (Exception: :meth:`ensure_writable` dispatches device page
    copies with the pools donated, matching the contract of every jitted
    model entry point — after calling it, the old cache's device arrays
    must not be reused.)

    ``refcounts`` makes pages shareable: each physical page counts its
    owners (page-table mappings plus prefix-index retentions) and is
    returned to ``free`` only when the count hits zero.  ``share`` maps
    another sequence's pages by refcount bump, ``ensure_writable`` performs
    copy-on-write before a shared page is written, and
    ``retain_pages``/``release_pages`` hold pages alive for a prompt-prefix
    index without any slot mapping them.

    ``lengths_host``/``page_table_host`` are host-side shadows of the device
    arrays, maintained by :class:`repro.serve.paged_lm.PagedLM` and
    ``allocate``/``release``; the scheduler reads them instead of syncing
    device state on the hot path.

    ``kv_dtype='int8'`` allocates int8 K/V pools plus fp32 *scale pools*
    (``k_scale``/``v_scale``, shape (L, P, page, KVH) — one scale per page
    token slot per KV head, the layout of ``ref.quantize_kv``).  The scale
    pools are donated alongside the K/V pools in every jitted entry point,
    and page bookkeeping (allocate/trim/release) needs no extra work: a
    physical page owns its scale rows, so remapping the page remaps its
    scales — eviction/replay rebuilds both bit-for-bit through the same
    quantize-on-write ops.
    """

    k_pages: jax.Array     # (L, P, page, KVH, hd) — int8 codes in int8 mode
    v_pages: jax.Array
    page_table: jax.Array  # (B, n_pages) physical ids
    lengths: jax.Array     # (B,)
    free: List[int]
    mapped: Optional[np.ndarray] = None  # (B,) pages currently mapped per slot
    lengths_host: Optional[np.ndarray] = None      # (B,) int32 shadow
    page_table_host: Optional[np.ndarray] = None   # (B, n_pages) int32 shadow
    k_scale: Optional[jax.Array] = None  # (L, P, page, KVH) fp32, int8 mode
    v_scale: Optional[jax.Array] = None
    refcounts: Optional[np.ndarray] = None  # (P,) owners per physical page

    #: kv_dtype name → pool dtype (None = the config's compute dtype).
    KV_DTYPES = {
        "fp32": jnp.float32, "float32": jnp.float32,
        "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
        "int8": jnp.int8,
    }

    @classmethod
    def create(cls, cfg: ArchConfig, batch: int, max_len: int, page: int = 64,
               tp: int = 1, pool_pages: Optional[int] = None,
               kv_dtype=None):
        """``kv_dtype`` is a name from :attr:`KV_DTYPES`, an actual dtype
        (e.g. a :class:`repro.serve.paged_lm.PagedLM`'s ``kv_dtype``,
        guaranteeing model/cache agreement), or ``None`` for the config's
        compute dtype."""
        q_heads, kv_heads = cfg.heads_for_tp(tp)
        n_pages_seq = max_len // page
        pool = pool_pages if pool_pages is not None else batch * n_pages_seq
        if kv_dtype is None:
            dt = cfg.compute_dtype
        elif isinstance(kv_dtype, str):
            dt = cls.KV_DTYPES[kv_dtype]
        else:
            dt = jnp.dtype(kv_dtype).type
        shape = (cfg.n_layers, pool, page, kv_heads, cfg.hd)
        quantized = dt == jnp.int8
        # Scale init of 1.0 matches ref.int8_quantize on all-zero rows, so an
        # unwritten page dequantizes to exact zeros either way.
        return cls(
            k_pages=jnp.zeros(shape, dt),
            v_pages=jnp.zeros(shape, dt),
            page_table=jnp.zeros((batch, n_pages_seq), jnp.int32),
            lengths=jnp.zeros((batch,), jnp.int32),
            free=list(range(pool)),
            mapped=np.zeros((batch,), np.int64),
            lengths_host=np.zeros((batch,), np.int32),
            page_table_host=np.zeros((batch, n_pages_seq), np.int32),
            k_scale=jnp.ones(shape[:-1], jnp.float32) if quantized else None,
            v_scale=jnp.ones(shape[:-1], jnp.float32) if quantized else None,
            refcounts=np.zeros((pool,), np.int64),
        )

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def pool_bytes(self) -> int:
        """Device bytes held by the K/V pools (scale pools included)."""
        total = self.k_pages.nbytes + self.v_pages.nbytes
        if self.quantized:
            total += self.k_scale.nbytes + self.v_scale.nbytes
        return total

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    @property
    def pages_per_seq(self) -> int:
        return self.page_table.shape[1]

    @property
    def total_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def n_free(self) -> int:
        return len(self.free)

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def _mapped(self, seq: int) -> int:
        if self.mapped is not None:
            return int(self.mapped[seq])
        if self.lengths_host is not None:
            return self.pages_for(int(self.lengths_host[seq]))
        ln = int(np.asarray(self.lengths)[seq])
        return self.pages_for(ln)

    def _host_table(self) -> np.ndarray:
        if self.page_table_host is not None:
            return np.array(self.page_table_host)
        return np.array(self.page_table)

    def _drop_ref(self, refs: Optional[np.ndarray], free: List[int],
                  page: int) -> None:
        """Drop one owner of ``page``; free it when no owners remain.

        With no refcount array (legacy caches built before sharing) every
        page has exactly one owner and the drop is an immediate free.
        """
        if refs is None:
            free.append(page)
            return
        refs[page] -= 1
        if refs[page] < 0:
            raise AssertionError(f"page {page} refcount went negative")
        if refs[page] == 0:
            free.append(page)

    def allocate(self, seq: int, n_pages: int) -> "PagedKVCache":
        """Map ``n_pages`` new physical pages after the slot's current ones."""
        if n_pages > len(self.free):
            raise OutOfPages(
                f"seq {seq} needs {n_pages} pages, {len(self.free)} free"
            )
        start = self._mapped(seq)
        if start + n_pages > self.pages_per_seq:
            raise OutOfPages(
                f"seq {seq}: {start}+{n_pages} pages exceeds the "
                f"{self.pages_per_seq}-page table row"
            )
        free = list(self.free)
        ids = [free.pop() for _ in range(n_pages)]
        refs = None if self.refcounts is None else self.refcounts.copy()
        if refs is not None:
            for p in ids:
                refs[p] = 1
        pt = self._host_table()
        pt[seq, start:start + n_pages] = ids
        mapped = None if self.mapped is None else self.mapped.copy()
        if mapped is not None:
            mapped[seq] = start + n_pages
        return dataclasses.replace(
            self, page_table=jnp.asarray(pt), page_table_host=pt,
            free=free, mapped=mapped, refcounts=refs,
        )

    def trim(self, seq: int, keep_pages: int) -> "PagedKVCache":
        """Unmap a slot's pages beyond ``keep_pages``.

        Only meaningful for pages past the written content (lookahead
        over-provisioning): trimmed pages hold no live KV *for this slot*,
        so remapping them later on demand is loss-free.  A trimmed page
        still referenced elsewhere (a prefix sibling or the prefix index)
        is only un-mapped here — it returns to the free pool when its last
        owner drops it.
        """
        used = self._mapped(seq)
        if keep_pages >= used:
            return self
        pt = self._host_table()
        free = list(self.free)
        refs = None if self.refcounts is None else self.refcounts.copy()
        for p in pt[seq, keep_pages:used]:
            self._drop_ref(refs, free, int(p))
        pt[seq, keep_pages:used] = 0
        mapped = None if self.mapped is None else self.mapped.copy()
        if mapped is not None:
            mapped[seq] = keep_pages
        return dataclasses.replace(
            self, page_table=jnp.asarray(pt), page_table_host=pt,
            free=free, mapped=mapped, refcounts=refs,
        )

    def release(self, seq: int) -> "PagedKVCache":
        """Drop a slot's page mappings (sequence exit / eviction).

        Each page loses this slot as an owner; pages with no remaining
        owners return to the free pool.
        """
        pt = self._host_table()
        used = self._mapped(seq)
        free = list(self.free)
        refs = None if self.refcounts is None else self.refcounts.copy()
        for p in pt[seq, :used]:
            self._drop_ref(refs, free, int(p))
        pt[seq, :] = 0
        if self.lengths_host is not None:
            lengths = self.lengths_host.copy()
        else:
            lengths = np.array(self.lengths)
        lengths[seq] = 0
        mapped = None if self.mapped is None else self.mapped.copy()
        if mapped is not None:
            mapped[seq] = 0
        return dataclasses.replace(
            self, page_table=jnp.asarray(pt), page_table_host=pt,
            lengths=jnp.asarray(lengths),
            lengths_host=lengths if self.lengths_host is not None else None,
            free=free, mapped=mapped, refcounts=refs,
        )

    # -- prefix sharing ------------------------------------------------------

    def share(self, seq: int, page_ids: List[int]) -> "PagedKVCache":
        """Map already-populated physical pages into ``seq`` by refcount bump.

        The pages' KV contents are untouched — the new sequence reads the
        prefix another sequence prefilled.  Writes into a shared page must
        go through :meth:`ensure_writable` first.
        """
        if not page_ids:
            return self
        if self.refcounts is None:
            raise ValueError("share() requires a refcounted cache")
        start = self._mapped(seq)
        if start + len(page_ids) > self.pages_per_seq:
            raise OutOfPages(
                f"seq {seq}: {start}+{len(page_ids)} shared pages exceeds "
                f"the {self.pages_per_seq}-page table row"
            )
        refs = self.refcounts.copy()
        for p in page_ids:
            if refs[p] <= 0:
                raise AssertionError(f"cannot share unowned page {p}")
            refs[p] += 1
        pt = self._host_table()
        pt[seq, start:start + len(page_ids)] = page_ids
        mapped = None if self.mapped is None else self.mapped.copy()
        if mapped is not None:
            mapped[seq] = start + len(page_ids)
        return dataclasses.replace(
            self, page_table=jnp.asarray(pt), page_table_host=pt,
            mapped=mapped, refcounts=refs,
        )

    def retain_pages(self, page_ids: List[int]) -> "PagedKVCache":
        """Add one owner to each page (prefix-index retention)."""
        if not page_ids:
            return self
        if self.refcounts is None:
            raise ValueError("retain_pages() requires a refcounted cache")
        refs = self.refcounts.copy()
        for p in page_ids:
            if refs[p] <= 0:
                raise AssertionError(f"cannot retain unowned page {p}")
            refs[p] += 1
        return dataclasses.replace(self, refcounts=refs)

    def release_pages(self, page_ids: List[int]) -> "PagedKVCache":
        """Drop one owner from each page; zero-owner pages return to free."""
        if not page_ids:
            return self
        if self.refcounts is None:
            raise ValueError("release_pages() requires a refcounted cache")
        refs = self.refcounts.copy()
        free = list(self.free)
        for p in page_ids:
            self._drop_ref(refs, free, int(p))
        return dataclasses.replace(self, refcounts=refs, free=free)

    def check_integrity(self, retained: int = 0) -> None:
        """Assert the pool's host-side bookkeeping is self-consistent.

        ``retained`` is the number of out-of-table owners (prefix-index
        retentions) the refcount conservation law must account for.  Checks
        — all host-side, no device sync:

        * the free list holds no duplicates and only valid page ids;
        * no page is simultaneously free and owned, and free + owned
          partition the pool (refcounted caches);
        * conservation: ``refcounts.sum() == mapped.sum() + retained``;
        * every mapped page-table entry points at an owned page, and
          entries beyond ``mapped`` are zeroed (no orphaned host shadows);
        * ``lengths_host`` never exceeds the mapped capacity of its slot.

        Raises ``AssertionError`` on the first violation; the chaos suite
        (``repro.serve.faults``) calls this after every scheduler step.
        """
        free = list(self.free)
        assert len(free) == len(set(free)), "duplicate pages in free list"
        assert all(0 <= p < self.total_pages for p in free), \
            f"free list holds out-of-range page: {free}"
        refs = self.refcounts
        table = self.page_table_host
        if refs is not None:
            assert (refs >= 0).all(), "negative refcount"
            owned = {p for p in range(self.total_pages) if refs[p] > 0}
            overlap = owned & set(free)
            assert not overlap, f"pages both free and owned: {sorted(overlap)}"
            assert len(owned) + len(free) == self.total_pages, (
                f"free ({len(free)}) + owned ({len(owned)}) pages do not "
                f"partition the {self.total_pages}-page pool"
            )
            if self.mapped is not None:
                assert int(refs.sum()) == int(self.mapped.sum()) + retained, (
                    f"refcount conservation broken: refs {int(refs.sum())} "
                    f"!= mapped {int(self.mapped.sum())} + retained {retained}"
                )
        if table is not None and self.mapped is not None:
            for seq in range(table.shape[0]):
                used = int(self.mapped[seq])
                for p in table[seq, :used]:
                    assert int(p) not in set(free), \
                        f"seq {seq} maps free page {int(p)}"
                    if refs is not None:
                        assert refs[int(p)] >= 1, \
                            f"seq {seq} maps unowned page {int(p)}"
                assert not table[seq, used:].any(), (
                    f"seq {seq}: orphaned table entries beyond its "
                    f"{used} mapped pages"
                )
                if self.lengths_host is not None:
                    ln = int(self.lengths_host[seq])
                    assert ln <= used * self.page_size, (
                        f"seq {seq}: length shadow {ln} exceeds "
                        f"{used} mapped pages"
                    )

    def ensure_writable(self, seq: int, lo_token: int,
                        hi_token: int) -> Tuple["PagedKVCache", int]:
        """Copy-on-write any shared page covering tokens [lo, hi] of ``seq``.

        Pages in the token range with more than one owner are copied to
        fresh physical pages (K/V pools and, in int8 mode, the scale pools
        — the codes and scales move together, so replay never re-quantizes
        differently) and the slot's table is re-pointed at the private
        copy.  Returns ``(cache, n_copied)``.  Device pools are donated
        into the copy dispatch, matching the model entry points.
        """
        if self.refcounts is None or lo_token > hi_token:
            return self, 0
        page = self.page_size
        p_lo = lo_token // page
        p_hi = min(hi_token // page, self._mapped(seq) - 1)
        if p_hi < p_lo:
            return self, 0
        table = (self.page_table_host if self.page_table_host is not None
                 else np.asarray(self.page_table))
        shared = [
            (pi, int(table[seq, pi]))
            for pi in range(p_lo, p_hi + 1)
            if self.refcounts[int(table[seq, pi])] > 1
        ]
        if not shared:
            return self, 0
        if len(shared) > len(self.free):
            raise OutOfPages(
                f"seq {seq}: copy-on-write needs {len(shared)} pages, "
                f"{len(self.free)} free"
            )
        refs = self.refcounts.copy()
        free = list(self.free)
        pt = self._host_table()
        kp, vp = self.k_pages, self.v_pages
        ks, vs = self.k_scale, self.v_scale
        copied = 0
        with _donation_noop_ok():
            for pi, src in shared:
                if refs[src] == 1:
                    # This slot mapped ``src`` twice and an earlier copy
                    # left it the page's last owner: write in place.
                    continue
                dst = free.pop()
                src_i = np.int32(src)
                dst_i = np.int32(dst)
                kp = _copy_pool_page(kp, src_i, dst_i)
                vp = _copy_pool_page(vp, src_i, dst_i)
                if ks is not None:
                    ks = _copy_pool_page(ks, src_i, dst_i)
                    vs = _copy_pool_page(vs, src_i, dst_i)
                refs[src] -= 1
                refs[dst] = 1
                pt[seq, pi] = dst
                copied += 1
        return dataclasses.replace(
            self, k_pages=kp, v_pages=vp, k_scale=ks, v_scale=vs,
            page_table=jnp.asarray(pt), page_table_host=pt,
            free=free, refcounts=refs,
        ), copied
