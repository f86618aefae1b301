"""Paged KV cache — the serving runtime's device-memory manager.

Long-context serving is memory-bound on the KV cache, and naive per-request
contiguous allocation at ``max_len`` wastes most of it: concurrent sequences
have ragged lengths, so reserving the worst case per slot strands HBM
(PagedAttention's motivating measurement — PAPERS.md [S1]). The fix is the
OS page-table design: the cache is a single pool of fixed-size **blocks**
(for multi-head attention's K and V ``[num_blocks, heads, block_size,
head_dim]`` per layer — heads ahead of
the block's tokens, so one head's page is a whole ``[block_size,
head_dim]`` tile, the shape the TPU's Pallas lowering can block and
DMA; in general whatever rows the model DECLARES, ``[num_blocks, *lead,
block_size, width]`` for a row ``(*lead, width)``, see
:class:`PagedKVCache`) and each sequence
holds an ordered **block table** of pool indices; allocation is
block-granular, so waste is bounded by one partial block per sequence and
freed blocks are immediately reusable by any other request.

Split of responsibilities (the framework's static-shapes contract):

- **Host side, dynamic**: :class:`BlockAllocator` (free-list alloc/free)
  and :class:`PagedKVCache` (device pools + the authoritative host mirror
  of block tables and lengths). Admission/eviction mutate ONLY these small
  host arrays between decode ticks — nothing here is traced.
- **Device side, pure**: :func:`gather_pages`, :func:`write_prefill`,
  :func:`write_token`, :func:`write_span` — a ``jnp``-pure gather and
  in-place row and page writes the compiled prefill/decode programs call
  with fixed shapes (:func:`scatter_prefill`, :func:`scatter_token` and
  :func:`scatter_span` are the XLA scatters they replaced: the tests'
  oracles, and what a quantized pool's one-shot prefill still runs).
  Block tables enter the
  compiled step as ordinary int32 operands, so the program never retraces
  as sequences come and go.

Block id 0 is reserved as the **null block**: unallocated table entries and
masked-off scatter rows all target it, so every scatter is total (no
dynamic shapes, no OOB) and its contents are unspecified-but-finite —
reads through it are always masked by the length before use.

**Copy-on-write prefix sharing** (ISSUE 12, PAPERS.md [S1][S4]): blocks
are REFCOUNTED, and a :class:`PrefixCache` maps content hashes of
block-aligned prompt prefixes to the physical blocks already holding
their KV. Admission walks the new prompt's full-block chain through the
cache; every hit is adopted by reference (incref — zero new HBM, zero
prefill scatter for those rows), and only the divergent tail allocates
fresh blocks. A sharer never writes a multiply-owned block: the engine
FORKS it first (allocate + device-copy the one block + decref the
original) — the classic COW page-table move, confined to the partial
boundary block at the divergence point. Eviction decrefs; a block
returns to the free list exactly once, when its LAST owner lets go, and
its cache entries are invalidated at that same moment — sharing is
between concurrently-resident sequences, so churn can never serve stale
pool bytes.

**Int8 KV quantization** (ISSUE 14, the capacity lever): with
``kv_dtype="int8"`` each pool stores symmetric int8 values plus a
per-block scale page ``[L, num_blocks, H, block_size]`` (one f32 scale
per token row per head — scales live at block granularity beside the
pools, per-row within the block so incremental writes NEVER requantize
resident tokens). Quantization happens on the way in
(:func:`quantize_rows` inside :func:`scatter_prefill_pages`,
:func:`write_token` and :func:`write_span`) and
dequantization inside the consumer — the Pallas kernels rescale blocks
in VMEM, the XLA path in :func:`gather_pages` — so HBM holds ~1 byte
per KV element instead of 4 and resident capacity roughly triples at
equal pool bytes (``kv_bytes_per_token`` is the exact accounting).

**Radix retention** (ISSUE 14, RadixAttention [S4]): with sharing on, a
prefix-cache-registered block whose LAST owner decrefs moves to a
**retained LRU** instead of the free list — its cache entries stay
valid, so a follow-up request with the same prompt prefix hits even
when no live sequence shares it. Retained blocks are RECLAIMABLE
capacity: ``alloc`` recycles them lazily (oldest first) only when the
free list runs dry, invalidating their entries at that moment, and
``free_blocks``/``admit_probe`` count ``free + retained`` so
backpressure and the autoscaler's load signal never shed against
capacity that one reclaim away exists. Adopting a retained block
increfs it straight out of the LRU (a *retained hit*).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["BlockAllocator", "PagedKVCache", "PoolGroup", "PrefixCache",
           "PrefixMatch",
           "gather_pages", "scatter_prefill", "scatter_token",
           "scatter_span", "scatter_prefill_pages", "write_token",
           "write_span", "write_prefill", "quantize_rows", "dequantize_rows",
           "pages_to_blobs", "blobs_to_pages", "NULL_BLOCK"]

# block 0 never holds live data: it is the scatter target for padding rows
# and the gather source for unallocated table entries (always masked)
NULL_BLOCK = 0


# ---------------------------------------------------------------------------
# device side: jnp-pure gather/scatter (called from compiled programs)
# ---------------------------------------------------------------------------

def quantize_rows(kv):
    """Symmetric per-row-per-head int8 quantization of KV projections:
    ``kv [..., hd]`` f32 -> ``(int8 [..., hd], scale [...])`` with
    ``scale = amax/127`` over the head_dim (the finest granularity that
    needs no requantization when later tokens land in the same block —
    the scale-granularity decision, DESIGN_DECISIONS PR-14)."""
    amax = jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(kv / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_rows(q, scale):
    """Inverse of :func:`quantize_rows`: ``int8 [..., hd] * scale [...]``
    -> f32 values."""
    return q.astype(jnp.float32) * scale[..., None]


def gather_pages(pages, table, layer=None):
    """Gather one layer's paged K (or V) into position order.

    ``pages`` ``[N, H, bs, hd]`` — or the stacked pools ``[L, N, H, bs,
    hd]`` with ``layer`` (a traced scalar is fine: the layer is one more
    gather index, nothing is sliced out of the pool) — ``table``
    ``[S, MB]`` int32 -> ``[S, MB*bs, H, hd]``: row ``s``'s tokens
    ``0..len-1`` in order, with unspecified (null-block / stale) content
    beyond the sequence length — the attention mask owns that boundary.
    A quantized pool — the tuple ``(int8 values, scales [N, H, bs])`` —
    gathers DEQUANTIZED f32 values, so every consumer downstream of the
    gather is dtype-oblivious."""
    idx = table if layer is None else (layer, table)
    if isinstance(pages, tuple):
        vals, scales = pages
        pages = dequantize_rows(vals[idx], scales[idx])
    else:
        pages = pages[idx]                            # [S, MB, H, bs, hd]
    S, MB, H, bs, hd = pages.shape
    return jnp.swapaxes(pages, 2, 3).reshape(S, MB * bs, H, hd)


def scatter_prefill(pages, kv, table, length, start=0):
    """Write a prefill's per-layer K (or V) rows into the paged pool.

    ``kv`` ``[B, W, H, hd]`` holds projections for positions ``0..W-1``
    (``W`` = the fixed padded prefill width); only rows in
    ``[start, length)`` are live — the rest are routed to the null
    block. ``start`` (scalar or ``[B]``) masks off a prefix-cache hit:
    shared rows already live in the donor's blocks and a sharer must
    never write a multiply-owned page (the COW discipline). Returns the
    updated pool. ``table`` ``[B, MB]``, ``length`` ``[B]``."""
    B, W = kv.shape[:2]
    bs = pages.shape[2]
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    pos = jnp.arange(W, dtype=jnp.int32)
    live = ((pos[None, :] < length[:, None])
            & (pos[None, :] >= start[:, None]))
    blk = jnp.where(live,
                    jnp.take_along_axis(table, pos[None, :] // bs, axis=1),
                    NULL_BLOCK)                                   # [B, W]
    off = jnp.broadcast_to(pos % bs, (B, W))
    # heads sit between the block id and the in-block offset; the two
    # index arrays broadcast to the front, so the update is kv's shape
    return pages.at[blk, :, off].set(kv)


def _token_route(table, position, active, bs):
    """``(blk [S], off [S])``: the pool block and in-block row the new
    token of every slot lands in; inactive slots route to the null
    block."""
    S = table.shape[0]
    blk = jnp.where(active, table[jnp.arange(S), position // bs],
                    NULL_BLOCK)
    return blk, position % bs


def _span_route(table, start, n, write_from, Q, bs):
    """``(blk [S, Q], off [S, Q])`` for token ``j`` of slot ``s`` at
    position ``start[s] + j``; rows ``j >= n[s]`` (draft padding, chunk
    tail) and positions below ``write_from`` route to the null block."""
    MB = table.shape[1]
    pos = start[:, None] + jnp.arange(Q, dtype=jnp.int32)[None, :]  # [S, Q]
    live = jnp.arange(Q, dtype=jnp.int32)[None, :] < n[:, None]
    if write_from is not None:
        live = live & (pos >= write_from[:, None])
    # clip the table index: masked-off rows may point past the table
    # width; they route to the null block anyway
    idx = jnp.clip(pos // bs, 0, MB - 1)
    blk = jnp.where(live, jnp.take_along_axis(table, idx, axis=1),
                    NULL_BLOCK)
    return blk, pos % bs


def scatter_token(pages, kv, table, position, active):
    """ONE layer's pool ``[N, H, bs, hd]`` with one decode step's K (or
    V) written for every slot, as an XLA scatter: the plain statement of
    what :func:`write_token` does in place on the stacked pools, kept as
    the oracle the tests hold it to. ``kv`` ``[S, H, hd]`` is the new
    token's projection per slot; ``position`` ``[S]`` the 0-based index
    it occupies (the sequence length BEFORE this token); inactive slots
    scatter to the null block."""
    blk, off = _token_route(table, position, active, pages.shape[2])
    return pages.at[blk, :, off].set(kv)


def scatter_span(pages, kv, table, start, n, write_from=None):
    """The multi-token generalization of :func:`scatter_token`, and
    :func:`write_span`'s oracle. ``kv`` ``[S, Q, H, hd]``: token ``j``
    of slot ``s`` lands at position ``start[s] + j``; only tokens
    ``j < n[s]`` are live. ``write_from`` ``[S]`` (optional)
    additionally masks positions below it — a chunk re-reading a fully
    shared prefix for its logits must not write the co-owned pages."""
    blk, off = _span_route(table, start, n, write_from, kv.shape[1],
                           pages.shape[2])
    return pages.at[blk, :, off].set(kv)


# The compiled programs' writes. The pools ``[L, N, H, bs, hd]`` are the
# CARRY of the tick's layer scan (and of the one-shot prefill's layer loop,
# :func:`write_prefill`), so a write has to leave them where they are: an
# XLA scatter over the carried pool makes the compiler give the whole carry
# a scatter-friendly layout and copy both pools to it and back every layer
# (DESIGN_DECISIONS, PR 26; once a prefill for the scatter under ``vmap``,
# PR 36). A row is therefore written as one ``dynamic_update_slice`` of a
# small slab, and a page as one of a whole page, which XLA does in place.

def _block_size(pages, lead=1):
    """``bs`` of stacked pools ``[L, N, *lead, bs, ...]`` (``lead`` axes
    of a token's row come ahead of the block's tokens: the heads of a K
    or V pool, none of a latent pool), plain or the quantized tuple."""
    return (pages[0] if isinstance(pages, tuple) else pages).shape[2 + lead]


def _write_rows(pages, layer, kv, blk, off, lead=1):
    """``kv [R, H, hd]`` written into layer ``layer`` of the stacked
    pools at ``(blk[r], :, off[r])``, one ``[1, 1, H, 1, hd]`` slab a
    row, in row order (the null block takes every masked row; what it
    holds is never read unmasked). Straight-line code whatever ``R``:
    inside a loop XLA re-lays an int8 pool round the writes. With
    ``lead=0`` a row is ``[width]`` and its slab ``[1, 1, 1, width]``
    at ``(layer, blk[r], off[r], 0)``."""
    def write(pool, rows):
        zeros = (0,) * (rows.ndim - 1 - lead)
        for r in range(rows.shape[0]):
            pool = lax.dynamic_update_slice(
                pool, jnp.expand_dims(rows[r], lead)[None, None],
                (layer, blk[r]) + (0,) * lead + (off[r],) + zeros)
        return pool

    if isinstance(pages, tuple):
        # int8 rows into the value pages, their per-row-per-head scales
        # into the scale pages, at the same block and offset
        return tuple(write(pool, rows)
                     for pool, rows in zip(pages, quantize_rows(kv)))
    return write(pages, kv.astype(pages.dtype))


def write_token(pages, layer, kv, table, position, active):
    """The stacked pools ``[L, N, H, bs, hd]`` (or the quantized tuple)
    with layer ``layer``'s new-token K (or V) ``kv [S, H, hd]`` written
    in place: :func:`scatter_token` on ``pages[layer]``, without taking
    the layer out. ``layer`` may be traced. A pool whose rows have no
    leading axis (``[L, N, bs, width]``, ``kv [S, width]``) is written
    the same way."""
    lead = kv.ndim - 2           # row axes ahead of the block's tokens
    blk, off = _token_route(table, position, active,
                            _block_size(pages, lead))
    return _write_rows(pages, layer, kv, blk, off, lead)


def _write_pages(pool, layer, rows, table, start, n, write_from):
    """A long span's rows ``[S, Q, width]`` written into layer ``layer``
    of a pool ``[L, N, bs, width]`` a PAGE at a time: the span of slot
    ``s`` touches at most ``ceil(Q / bs) + 1`` pages from the one that
    holds ``start[s]`` on; each is read, its live rows (``start <= position
    < start + n``, and not below ``write_from``) replaced, and written
    back whole as one ``dynamic_update_slice``; a page with no live row is
    the null block's. Straight-line code, in place like the row writes: 33
    page writes for a 512-row chunk where the row writes were 512 (2,560
    a five-layer chunk: 79 s of compile, PR 29)."""
    S, Q, W = rows.shape
    bs, MB = pool.shape[2], table.shape[1]
    span = -(-Q // bs) + 1
    rows = rows.astype(pool.dtype)
    for s in range(S):
        first, shift = start[s] // bs, start[s] % bs
        padded = lax.dynamic_update_slice(
            jnp.zeros((span * bs, W), pool.dtype), rows[s], (shift, 0))
        pos = first * bs + jnp.arange(span * bs, dtype=jnp.int32)
        live = (pos >= start[s]) & (pos < start[s] + n[s])
        if write_from is not None:
            live = live & (pos >= write_from[s])
        for j in range(span):
            rows_live = live[j * bs:(j + 1) * bs]
            blk = jnp.where(jnp.any(rows_live),
                            table[s, jnp.minimum(first + j, MB - 1)],
                            NULL_BLOCK)
            old = lax.dynamic_slice(pool, (layer, blk, 0, 0), (1, 1, bs, W))
            page = jnp.where(rows_live[None, None, :, None],
                             padded[j * bs:(j + 1) * bs][None, None], old)
            pool = lax.dynamic_update_slice(pool, page, (layer, blk, 0, 0))
    return pool


def _write_fresh_pages(pool, layer, rows, table, start, n, write_from):
    """A long span's rows ``[S, Q, H, hd]`` written into layer ``layer``
    of a pool ``[L, N, H, bs, hd]`` WITHOUT reading it: the page that
    holds the first written position (``lo``: ``start``, or ``write_from``
    where that is later) takes its rows one at a time, as a tick's do, and
    every later page is written WHOLE, rows past the span's end as zeros.
    What such a row held is never read again: its position lies at or past
    the sequence's end, where every reader masks and the row that comes to
    lie there is written first (in a window group's ring it stands for a
    position ``ring * bs`` older, behind every window). ``bs`` row writes
    and ``ceil(Q / bs)`` page writes, all plain ``dynamic_update_slice``s
    that no compiler has a reason to move a pool for: the page writes of
    :func:`_write_pages` read each old page, and fused pairwise over a
    ``k`` and a ``v`` pool XLA copied one of them whole."""
    S, Q = rows.shape[:2]
    bs, MB = pool.shape[3], table.shape[1]
    pages = -(-Q // bs)
    zeros = (0,) * (rows.ndim - 2)
    for s in range(S):
        lo = start[s] if write_from is None \
            else jnp.maximum(start[s], write_from[s])
        hi = start[s] + n[s]
        own = jnp.pad(rows[s].astype(pool.dtype),
                      ((0, bs),) + ((0, 0),) * len(zeros))

        def rows_at(position):
            """``bs`` of the span's rows from ``position`` on."""
            return lax.dynamic_slice(
                own, (jnp.clip(position - start[s], 0, Q),) + zeros,
                (bs,) + own.shape[1:])

        def block_of(position, live):
            return jnp.where(live, table[s, jnp.minimum(position // bs,
                                                        MB - 1)], NULL_BLOCK)

        head = rows_at(lo)
        for i in range(bs):
            pos = lo + i
            live = (pos < hi) & (pos // bs == lo // bs)
            pool = lax.dynamic_update_slice(
                pool, head[i][None, None, :, None],
                (layer, block_of(pos, live), 0, pos % bs, 0))
        for j in range(pages):
            first = (lo // bs + 1 + j) * bs
            live = first + jnp.arange(bs, dtype=jnp.int32) < hi
            page = jnp.where(live[:, None, None], rows_at(first), 0)
            pool = lax.dynamic_update_slice(
                pool, jnp.moveaxis(page, 0, 1)[None, None],
                (layer, block_of(first, live[0]), 0, 0, 0))
    return pool


def write_span(pages, layer, kv, table, start, n, write_from=None,
               by_page: bool = False):
    """:func:`scatter_span` on ``pages[layer]`` in place: ``kv``
    ``[S, Q, H, hd]`` (or ``[S, Q, width]``), one row write a token; a
    span of two pages and more into a pool of plain ``[width]`` rows goes
    a page at a time (:func:`_write_pages`), and so does one into a plain
    (not quantized) pool of ``[H, hd]`` rows where the caller asks
    (``by_page``: :func:`_write_fresh_pages`, which leaves zeros in the
    rows of its last page that lie past the span's end)."""
    S, Q = kv.shape[:2]
    lead = kv.ndim - 3
    if Q >= 2 * _block_size(pages, lead):
        if lead == 0:
            return _write_pages(pages, layer, kv, table, start, n,
                                write_from)
        if by_page and not isinstance(pages, tuple):
            return _write_fresh_pages(pages, layer, kv, table, start, n,
                                      write_from)
    blk, off = _span_route(table, start, n, write_from, Q,
                           _block_size(pages, lead))
    return _write_rows(pages, layer, kv.reshape(S * Q, *kv.shape[2:]),
                       blk.reshape(S * Q), off.reshape(S * Q), lead)


def scatter_prefill_pages(pages, kv, table, length, start=0):
    """:func:`scatter_prefill`, quantizing on the way into a quantized
    ``(values, scales)`` pool (the scatter is shape-agnostic past the
    ``[blocks, heads, block_size]`` prefix, so the scale pages take the
    same routing)."""
    if isinstance(pages, tuple):
        vals, scales = pages
        q, s = quantize_rows(kv)
        return (scatter_prefill(vals, q, table, length, start),
                scatter_prefill(scales, s, table, length, start))
    return scatter_prefill(pages, kv.astype(pages.dtype), table, length,
                           start)


# pages a trip of the one-shot prefill's page loop writes (write_prefill)
_PAGES_A_TRIP = 8


def write_prefill(pages, kv, table, length, start):
    """:func:`scatter_prefill_pages` on every layer of the stacked pools
    ``[L, N, H, bs, hd]``, IN PLACE: ``kv [L, 1, W, H, hd]`` is a one-shot
    prefill's projections for positions ``0..W-1`` of ONE sequence,
    ``table [1, MB]``, ``length`` / ``start`` ``[1]``; rows in
    ``[start, length)`` are written and no row below ``start`` (a shared
    prefix's rows are co-owned: the copy-on-write discipline).

    A plain pool is the carry of a loop over the layers, and every write
    in it is one WHOLE page ``[H, bs, hd]`` put down by a plain
    ``dynamic_update_slice``. The page that holds ``start`` may be partly
    someone else's (an exact duplicate's boundary block ends inside it),
    so it alone is read first and only its live rows replaced; every later
    page is written unread, rows past ``length`` as zeros (every reader
    masks them and a tick writes a row before it is read), and a page with
    no live row is the null block's. XLA leaves such a pool where it is,
    float32 or bfloat16, where the scatter under ``vmap`` had both pools
    copied whole to its layout and back; ROW writes in the loop (what
    :func:`write_span` makes of the first page) would have a bfloat16 pool
    re-laid round it (DESIGN_DECISIONS, PR 36). A quantized ``(values,
    scales)`` pool keeps the scatter: no cell runs one."""
    if isinstance(pages, tuple):
        return jax.vmap(scatter_prefill_pages,
                        in_axes=(0, 0, None, None, None))(
                            pages, kv, table, length, start)
    L, B, W, H, hd = kv.shape
    bs, MB = pages.shape[3], table.shape[1]
    assert B == 1 and W % bs == 0, "one sequence, whole pages"
    lo, hi = start[0], length[0]
    slots = jnp.arange(bs, dtype=jnp.int32)

    def block_of(first, live):
        return jnp.where(live, table[0, jnp.minimum(first // bs, MB - 1)],
                         NULL_BLOCK)

    first = lo // bs * bs
    live_first = (first + slots >= lo) & (first + slots < hi)
    block_first = block_of(first, jnp.any(live_first))

    def layer(i, pool):
        own = kv[i, 0].astype(pool.dtype)

        def page_at(position):
            """The page ``[H, bs, hd]`` of rows from ``position`` on (past
            ``W``: the last page's, where no row is live)."""
            rows = lax.dynamic_slice(own, (position, 0, 0), (bs, H, hd))
            return jnp.moveaxis(rows, 0, 1)

        old = lax.dynamic_slice(pool, (i, block_first, 0, 0, 0),
                                (1, 1, H, bs, hd))
        pool = lax.dynamic_update_slice(
            pool, jnp.where(live_first[:, None], page_at(first), old),
            (i, block_first, 0, 0, 0))

        def later(j, pool):
            at = first + (1 + j) * bs
            live = at + slots < hi
            return lax.dynamic_update_slice(
                pool, jnp.where(live[:, None], page_at(at), 0)[None, None],
                (i, block_of(at, live[0]), 0, 0, 0))

        # page 0 is the first at the earliest: W / bs - 1 later ones reach W
        trips = W // bs - 1
        return lax.fori_loop(0, trips, later, pool,
                             unroll=min(_PAGES_A_TRIP, max(trips, 1)))

    return lax.fori_loop(0, L, layer, pages)


# ---------------------------------------------------------------------------
# cross-replica handoff serialization (ISSUE 18)
# ---------------------------------------------------------------------------
#
# Prefill/decode disaggregation ships a finished prompt's KV pages to a
# decode replica BLOCK BY BLOCK: the paged layout already made the block
# the unit of allocation, sharing and eviction, so it is the natural wire
# unit too — one binary transport frame per block, raw pool bytes (int8
# values + f32 scales when quantized — the receiver adopts them verbatim,
# so dequantization is bit-identical and the quantized wire cost is
# exactly the ~2.7x-smaller `bytes_per_block`). No base64, no JSON.

def pages_to_blobs(kpages, vpages) -> List[bytes]:
    """Serialize exported pages (``[L, nb, H, bs, hd]`` arrays, or
    ``(values, scales)`` tuples when quantized) into one ``bytes`` blob
    per block: K leaves then V leaves, each C-contiguous. The inverse is
    :func:`blobs_to_pages`; each blob is exactly ``bytes_per_block``
    long, which is what the wire-byte accounting audits against."""
    kleaves = list(kpages) if isinstance(kpages, tuple) else [kpages]
    vleaves = list(vpages) if isinstance(vpages, tuple) else [vpages]
    nb = int(np.asarray(kleaves[0]).shape[1])
    out = []
    for j in range(nb):
        parts = [np.ascontiguousarray(np.asarray(a)[:, j]).tobytes()
                 for a in kleaves + vleaves]
        out.append(b"".join(parts))
    return out


def blobs_to_pages(blobs: List[bytes], *, num_layers: int,
                   block_size: int, num_heads: int, head_dim: int,
                   quantized: bool, dtype="float32"):
    """Rebuild ``(kpages, vpages)`` pool page arrays from per-block wire
    blobs. The receiver supplies ITS OWN pool geometry — a blob whose
    length disagrees means the fleet is not homogeneous, and adopting it
    would scatter garbage, so that is a hard :class:`ValueError` (the
    adopt path refuses the handoff; the request resubmits)."""
    if not blobs:
        raise ValueError("handoff carries zero page blobs")
    L, bs, H, hd = num_layers, block_size, num_heads, head_dim
    if quantized:
        specs = [((L, H, bs, hd), np.dtype(np.int8)),
                 ((L, H, bs), np.dtype(np.float32))]
    else:
        specs = [((L, H, bs, hd), np.dtype(dtype))]
    leaf_bytes = [int(np.prod(s)) * d.itemsize for s, d in specs]
    per_blob = 2 * sum(leaf_bytes)
    nleaves = len(specs)
    cols: List[List[np.ndarray]] = [[] for _ in range(2 * nleaves)]
    for blob in blobs:
        if len(blob) != per_blob:
            raise ValueError(
                f"handoff blob is {len(blob)} bytes but this pool's "
                f"geometry expects {per_blob} — replica pool shapes "
                f"disagree")
        off = 0
        for i in range(2 * nleaves):
            shape, d = specs[i % nleaves]
            cols[i].append(np.frombuffer(
                blob, dtype=d, count=int(np.prod(shape)),
                offset=off).reshape(shape))
            off += leaf_bytes[i % nleaves]
    kleaves = [np.stack(cols[i], axis=1) for i in range(nleaves)]
    vleaves = [np.stack(cols[nleaves + i], axis=1)
               for i in range(nleaves)]
    k = tuple(kleaves) if quantized else kleaves[0]
    v = tuple(vleaves) if quantized else vleaves[0]
    return k, v


# ---------------------------------------------------------------------------
# host side: allocation / free (between-tick bookkeeping, never traced)
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Refcounted free-list allocator over pool block ids
    ``1..num_blocks-1`` (block 0 is the reserved null block). FIFO reuse
    keeps churn deterministic — tests pin that re-admitted sequences
    land on recycled blocks.

    Refcounts are what make physical prefix sharing safe: ``alloc``
    hands out blocks at refcount 1, a prefix-cache hit ``incref``\\ s the
    donor's block instead of allocating, and ``decref`` returns a block
    to the free list exactly once — when its LAST owner drops it. The
    legacy ``free`` is a decref loop, so single-owner code paths keep
    their exact historical behavior.

    **Retention** (ISSUE 14): ``decref(block, retain=True)`` parks a
    last-owner block in the retained LRU instead of freeing it — its KV
    pages stay addressable through the prefix cache for future
    admissions. Retained blocks are reclaimable capacity, not leaks:
    ``alloc`` recycles them lazily (oldest retained first, after the
    genuinely-free list) through ``reclaim_hook`` so the owning cache
    invalidates their entries at exactly the recycle moment, and every
    retained block still lands on the free list exactly once per
    retention episode. ``incref`` of a retained block REVIVES it out of
    the LRU at refcount 1 (the retained-hit path)."""

    def __init__(self, num_blocks: int):
        assert num_blocks >= 2, "need at least one non-null block"
        self.num_blocks = num_blocks
        self._free = collections.deque(range(1, num_blocks))
        self._rc: Dict[int, int] = {}
        # retained LRU: block -> True, insertion-ordered (oldest first);
        # rc == 0 for every member, but the block is NOT on the free
        # list — the prefix cache still maps its content
        self._retained: "collections.OrderedDict[int, bool]" = \
            collections.OrderedDict()
        # invoked with a block id the moment a retained block is
        # recycled onto the free list (the cache invalidation weld)
        self.reclaim_hook = None
        self.retained_reclaims = 0
        # cumulative alloc counter: the "fresh blocks" denominator the
        # sharing tests/bench diff against (adoptions don't bump it)
        self.total_allocs = 0

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_retained(self) -> int:
        return len(self._retained)

    @property
    def reclaimable(self) -> int:
        """The pool's REAL spare capacity: free plus lazily-reclaimable
        retained blocks — what admission/backpressure must count."""
        return len(self._free) + len(self._retained)

    def is_retained(self, block: int) -> bool:
        return block in self._retained

    def ref_count(self, block: int) -> int:
        """Current owner count (0 for free and retained blocks)."""
        return self._rc.get(block, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` block ids at refcount 1, or None (and no change)
        if unavailable. The free list serves first (FIFO — churn stays
        deterministic); under pressure, retained blocks are reclaimed
        oldest-first, each invalidated via ``reclaim_hook`` as it is
        recycled."""
        if n > self.reclaimable:
            return None
        while len(self._free) < n:
            blk, _ = self._retained.popitem(last=False)   # LRU victim
            if self.reclaim_hook is not None:
                self.reclaim_hook(blk)
            self._free.append(blk)
            self.retained_reclaims += 1
        got = [self._free.popleft() for _ in range(n)]
        for b in got:
            self._rc[b] = 1
        self.total_allocs += len(got)
        return got

    def incref(self, block: int) -> bool:
        """Adopt an allocated block (a prefix-cache hit: one more owner
        of the same physical pages). A RETAINED block revives out of the
        LRU at refcount 1; returns True exactly for that case (the
        retained-hit signal the telemetry counts)."""
        assert block != NULL_BLOCK, "cannot adopt the null block"
        if block in self._retained:
            del self._retained[block]
            self._rc[block] = 1
            return True
        assert self._rc.get(block, 0) > 0, \
            f"incref of unallocated block {block}"
        self._rc[block] += 1
        return False

    def decref(self, block: int, retain: bool = False) -> bool:
        """Drop one ownership; returns True when this was the LAST owner
        and the block left the refcounted set — onto the free list, or
        into the retained LRU with ``retain=True`` (prefix-registered
        blocks whose KV should outlive the sequence)."""
        assert block != NULL_BLOCK, "cannot free the null block"
        rc = self._rc.get(block, 0)
        assert rc > 0, f"decref of free block {block} (double free)"
        if rc > 1:
            self._rc[block] = rc - 1
            return False
        del self._rc[block]
        if retain:
            self._retained[block] = True
        else:
            self._free.append(block)
        return True

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            self.decref(b)


@dataclasses.dataclass
class PrefixMatch:
    """One admission's prefix-cache verdict: the physical blocks to
    adopt by reference (in table order) and the token count they cover.
    ``partial`` flags that the LAST adopted block is the donor's partial
    boundary block (shared mid-block — the engine must fork it before
    any write lands there: the copy-on-write point)."""
    blocks: List[int]
    length: int
    partial: bool = False

    @property
    def hit_blocks(self) -> int:
        return len(self.blocks)


class PrefixCache:
    """Content-addressed index of resident prompt-prefix KV blocks
    (the RadixAttention idea [S4] at block granularity).

    Keys are CUMULATIVE hashes: ``h_i = H(h_{i-1}, tokens[i*bs:(i+1)*bs])``
    — a chain hit guarantees the whole prefix matches, not just one
    block's tokens, so two prompts can never alias through a colliding
    interior block. Full blocks map ``h_i -> block``; the boundary
    partial block of a registered prompt maps ``(h_parent, tail_tokens)
    -> block`` and is only shared on an EXACT tail match (a duplicate
    prompt — retry storms, identical few-shot calls), because a sharer
    reads every row below its own length and will write the rest: any
    non-exact partial share would fork immediately for zero saved work.

    The cache holds NO references of its own: entries are invalidated
    the moment their block's last owner decrefs it (``PagedKVCache``
    wires the hook), so a recycled block can never serve stale bytes
    and every block still returns to the free list exactly once."""

    def __init__(self, block_size: int):
        self.block_size = block_size
        self._full: Dict[Tuple, int] = {}
        self._partial: Dict[Tuple, int] = {}
        self._by_block: Dict[int, List[Tuple[str, Tuple]]] = {}

    def __len__(self) -> int:
        return len(self._full) + len(self._partial)

    @staticmethod
    def _chain(parent, chunk) -> Tuple:
        return (parent, tuple(int(t) for t in chunk))

    def match(self, tokens: List[int]) -> PrefixMatch:
        """Longest resident prefix of ``tokens``: full-block chain hits,
        then an exact-tail partial boundary hit."""
        bs = self.block_size
        blocks: List[int] = []
        parent: Tuple = ()
        nf = len(tokens) // bs
        for i in range(nf):
            key = self._chain(parent, tokens[i * bs:(i + 1) * bs])
            b = self._full.get(key)
            if b is None:
                break
            blocks.append(b)
            parent = key
        matched = len(blocks) * bs
        rem = tokens[matched:]
        if len(blocks) == nf and rem:
            pb = self._partial.get(self._chain(parent, rem))
            if pb is not None:
                return PrefixMatch(blocks + [pb], len(tokens),
                                   partial=True)
        return PrefixMatch(blocks, matched)

    def register(self, tokens: List[int], blocks: List[int]) -> int:
        """Publish a freshly-prefilled prompt's blocks (table order).
        First writer wins — duplicate content keeps the existing entry
        so concurrent owners converge on ONE physical block chain.
        Returns how many new entries were added."""
        bs = self.block_size
        added = 0
        parent: Tuple = ()
        nf = len(tokens) // bs
        for i in range(nf):
            key = self._chain(parent, tokens[i * bs:(i + 1) * bs])
            if key not in self._full:
                self._full[key] = blocks[i]
                self._by_block.setdefault(blocks[i], []).append(
                    ("full", key))
                added += 1
            parent = key
        rem = tokens[nf * bs:]
        if rem and nf < len(blocks):
            key = self._chain(parent, rem)
            if key not in self._partial:
                self._partial[key] = blocks[nf]
                self._by_block.setdefault(blocks[nf], []).append(
                    ("partial", key))
                added += 1
        return added

    def covers(self, block: int) -> bool:
        """Whether any entry resolves to ``block`` — the retention
        eligibility test (only registered blocks are worth retaining:
        an unregistered block is unreachable through the cache)."""
        return block in self._by_block

    def invalidate_block(self, block: int) -> None:
        """Drop every entry resolving to ``block`` (its last owner just
        freed it, or the allocator reclaimed it from the retained LRU —
        the pool may recycle the pages any time now)."""
        for kind, key in self._by_block.pop(block, ()):
            table = self._full if kind == "full" else self._partial
            if table.get(key) == block:
                del table[key]


@dataclasses.dataclass
class PoolGroup:
    """One declared group of pools (:class:`PagedKVCache`): the layers of
    one KIND. ``ring`` is the blocks a slot owns of a window group
    (``ceil(window / block_size) + 1``: a window of positions touches at
    most that many pages), None for a group that grows with the context;
    ``rows`` the shape of one token's row in one layer, a pool. A STATE
    group (``state``) is no paged group: ``rows`` is then the state ONE
    SLOT keeps in one layer, whatever its length, and the group has no
    blocks and no table."""
    name: str
    layers: int
    window: Optional[int]
    ring: Optional[int]
    num_blocks: int
    rows: Dict[str, Tuple[int, ...]]
    state: bool = False

    def row_bytes(self, dtype) -> int:
        """Bytes one token leaves in ONE layer of the group."""
        return jnp.dtype(dtype).itemsize * sum(
            int(np.prod(r)) for r in self.rows.values())


class PagedKVCache:
    """Device pools + the authoritative host mirror of block tables and
    sequence lengths for up to ``max_slots`` concurrent sequences.

    ``pools`` maps each pool's name to its device array: a model
    DECLARES the paged state it keeps as named pools, each with the shape
    of ONE token's row in one layer (``row_shapes``), and a row shape
    ``(*lead, width)`` is allocated as ``[L, num_blocks, *lead,
    block_size, width]``: the block's tokens sit ahead of the row's last
    axis, so a page's last two dimensions are a whole ``[block_size,
    width]`` tile. By default (``row_shapes=None``) the pools are
    multi-head attention's ``k`` and ``v`` with rows ``(H, hd)``:
    ``[L, num_blocks, H, block_size, hd]``, also reachable as
    ``cache.k`` / ``cache.v``; a latent-attention model declares one
    pool ``latent`` with rows ``(width,)``: ``[L, num_blocks, block_size,
    width]``. The leading layer axis is the one the model's layer loop
    indexes: it carries every layer's pool whole, writes rows in place
    and reads pages by layer index. The compiled programs DONATE and
    return the pools; the engine reassigns ``cache.pools`` each call.
    Tables/lengths live here as small host numpy arrays — admission and
    eviction are plain host mutations between ticks, and know nothing of
    what a row holds.

    Int8 quantization, head sharding (``tp_degree``, ``shard_pools``) and
    the page export / import of a handoff are defined for the ``k`` /
    ``v`` pools only and refuse any other declaration.

    Pool GROUPS (``groups``): a model whose layers are of several kinds
    declares ``{group: {"layers": n, "pools": {name: row}, "window":
    w}}`` (``window`` optional) in place of ``num_layers`` and
    ``row_shapes``; the pools are then named ``<group>/<name>`` and each
    group is sized apart. A group with no window grows with the context:
    ``num_blocks`` blocks, and the tables, the allocator and the free
    count above are ITS account (several such groups grow alike and
    share it). A WINDOW group is a ring every slot owns outright:
    ``ceil(window / block_size) + 1`` blocks a slot
    (:attr:`PoolGroup.ring`), whatever the context, in a table that never
    changes: position ``p`` lives in the slot's ring block ``(p //
    block_size) % ring``, so the entry points and the kernels address it
    through an ordinary ``[max_slots, max_blocks_per_seq]`` table whose
    entries repeat with period ``ring``, and read no position older than
    ``window`` (what lies there has been overwritten). It has no
    allocator and nothing to free, and it never refuses an admission.
    Prefix sharing and copy-on-write do not reach a ring (the engine
    refuses them at build).

    A STATE group (``{"layers": n, "pools": {name: row}, "state": True}``)
    holds what a layer keeps of a slot's past whatever its length (a short
    convolution's last inputs): each pool is ONE array ``[n, max_slots,
    *row]``, a slot's rows at its own index, with no blocks, no allocator
    and no table. A call's rows are consecutive slots, and the entry
    points take the first of them, a scalar, as that group's "table": 0
    for a tick (every slot), the slot for a prefill chunk
    (:meth:`device_tables`, :meth:`slot_tables`). Nothing here resets or copies a slot's state: a
    prefill that starts at position 0 starts from zeros, inside the
    program that writes it. Prefix sharing does not reach it (a shared
    page would need the state at its end), and :meth:`group_facts` and
    ``kv_bytes_per_token`` count the paged groups only; :meth:`state_facts`
    counts it."""

    def __init__(self, num_layers: Optional[int], num_heads: Optional[int],
                 head_dim: Optional[int],
                 num_blocks: int, block_size: int, max_slots: int,
                 max_blocks_per_seq: int, dtype=jnp.float32,
                 share_prefix: bool = False, kv_dtype: Optional[str] = None,
                 retain_prefix: bool = True, tp_degree: int = 1,
                 row_shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
                 groups: Optional[Dict[str, Dict]] = None):
        self.groups: Dict[str, PoolGroup] = {}
        if groups:
            assert row_shapes is None, "declare pools or groups, not both"
            for g, spec in groups.items():
                window = spec.get("window")
                state = bool(spec.get("state"))
                ring = None if window is None \
                    else -(-int(window) // block_size) + 1
                self.groups[g] = PoolGroup(
                    g, int(spec["layers"]), window, ring,
                    max_slots if state else
                    num_blocks if ring is None else max_slots * ring + 1,
                    {n: tuple(int(d) for d in r)
                     for n, r in spec["pools"].items()}, state)
            num_layers = sum(g.layers for g in self.groups.values())
            row_shapes = {f"{g.name}/{n}": r for g in self.groups.values()
                          for n, r in g.rows.items()}
            if share_prefix and any(g.ring or g.state
                                    for g in self.groups.values()):
                raise ValueError("prefix sharing does not reach a window "
                                 "group's ring or a state group")
        self.num_layers = num_layers
        self.kv_pools = row_shapes is None
        if self.kv_pools:
            row_shapes = {"k": (num_heads, head_dim),
                          "v": (num_heads, head_dim)}
        else:
            if kv_dtype == "int8" or tp_degree != 1:
                raise ValueError(
                    f"int8 pools and head-sharded pools are defined for "
                    f"the k / v pools of multi-head attention only; this "
                    f"model declares {sorted(row_shapes)}")
            num_heads, head_dim = 1, None
        self.row_shapes = {n: tuple(int(d) for d in r)
                           for n, r in row_shapes.items()}
        self.num_heads = num_heads
        self.head_dim = head_dim
        # tensor-parallel degree (ISSUE 15): the pools are LOGICALLY
        # [L, N, H, bs, hd] but physically head-sharded over a tp mesh
        # (`shard_pools`), so every per-byte accounting number here is
        # PER SHARD — each device holds H/tp heads of every block, and
        # capacity at equal per-device HBM scales with the mesh. Tables,
        # lengths and the allocator stay shard-oblivious: a block id
        # names the same logical block on every shard.
        if num_heads % tp_degree:
            raise ValueError(f"num_heads {num_heads} must divide by "
                             f"tp_degree {tp_degree}")
        self.tp_degree = int(tp_degree)
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_slots = max_slots
        self.max_blocks_per_seq = max_blocks_per_seq
        self.dtype = dtype
        if kv_dtype not in (None, "f32", "float32", "int8"):
            raise ValueError(f"kv_dtype must be None|'f32'|'int8', "
                             f"got {kv_dtype!r}")
        self.quantized = kv_dtype == "int8"
        self.pools: Dict[str, object] = {}
        for name, row in self.row_shapes.items():
            group = self.groups.get(name.split("/")[0])
            layers, blocks = ((num_layers, num_blocks) if group is None
                              else (group.layers, group.num_blocks))
            shape = ((layers, blocks, *row) if group and group.state
                     else (layers, blocks, *row[:-1], block_size, row[-1]))
            if self.quantized:
                # int8 value pages + per-block scale pages (one f32 per
                # token row per head) — quantize-on-scatter writes both
                # through the same block/offset routing
                self.pools[name] = (jnp.zeros(shape, jnp.int8),
                                    jnp.zeros(shape[:-1], jnp.float32))
            else:
                self.pools[name] = jnp.zeros(shape, dtype)
        self.allocator = BlockAllocator(num_blocks)
        self.tables = np.zeros((max_slots, max_blocks_per_seq), np.int32)
        self.lengths = np.zeros((max_slots,), np.int32)
        # a window group's table: slot s owns blocks 1 + s * ring ..,
        # page j of any context is ring block j % ring; made once, on the
        # host (a chunk takes one row of it) and on the device (a tick
        # takes it whole, the same array every time)
        self._ring_tables = {
            g.name: (1 + np.arange(max_slots)[:, None] * g.ring
                     + np.arange(max_blocks_per_seq)[None, :] % g.ring
                     ).astype(np.int32)
            for g in self.groups.values() if g.ring}
        self._ring_device = {n: jnp.asarray(t)
                             for n, t in self._ring_tables.items()}
        # a state group's operand: the first of the consecutive slots a
        # call's rows are (a tick's rows are every slot, from 0)
        self._first_slot = jnp.zeros((), jnp.int32)
        self._owned: List[List[int]] = [[] for _ in range(max_slots)]
        # COW bookkeeping: which table indices this slot ADOPTED (vs
        # allocated), and the admission-reserved fork target
        self._adopted: List[set] = [set() for _ in range(max_slots)]
        self._fork_reserve: List[Optional[int]] = [None] * max_slots
        self.share_prefix = share_prefix
        self.prefix_cache = PrefixCache(block_size) if share_prefix \
            else None
        # radix retention (ISSUE 14): registered blocks outlive their
        # last owner in the allocator's retained LRU; the reclaim hook
        # welds cache invalidation to the lazy recycle moment
        self.retain_prefix = bool(retain_prefix and share_prefix)
        if self.prefix_cache is not None:
            self.allocator.reclaim_hook = \
                self.prefix_cache.invalidate_block
        # cumulative sharing counters (telemetry feeds off these)
        self.prefix_hit_blocks = 0
        self.cow_forks = 0
        self.retained_hits = 0

    # -- derived -----------------------------------------------------------

    @property
    def k(self):
        return self.pools["k"]

    @k.setter
    def k(self, value):
        self.pools["k"] = value

    @property
    def v(self):
        return self.pools["v"]

    @v.setter
    def v(self, value):
        self.pools["v"] = value

    def _kv_only(self, what: str) -> None:
        if not self.kv_pools:
            raise NotImplementedError(
                f"{what} is defined for the k / v pools of multi-head "
                f"attention only; this cache holds {sorted(self.pools)}")

    @property
    def context_width(self) -> int:
        """The fixed gather width ``max_blocks_per_seq * block_size`` —
        the maximum context length a slot can hold, and the padded width
        every prefill/decode attention runs at."""
        return self.max_blocks_per_seq * self.block_size

    @property
    def free_blocks(self) -> int:
        """Spare pool capacity INCLUDING lazily-reclaimable retained
        blocks — the number admission, backpressure, and the fleet's
        load signal must use (a retained block is one reclaim away from
        free; counting only the raw free list would shed spuriously)."""
        return self.allocator.reclaimable

    @property
    def retained_blocks(self) -> int:
        """Blocks currently parked in the retained LRU (rc 0, prefix
        cache still maps their content)."""
        return self.allocator.num_retained

    @property
    def quant_dtype(self) -> str:
        return "int8" if self.quantized else jnp.dtype(self.dtype).name

    @property
    def kv_bytes_per_token(self) -> int:
        """HBM bytes one resident token costs across all layers, K and
        V, PER SHARD: the capacity accounting behind the int8 ~3-4x win
        (values at 1 byte + one f32 scale per head vs 4 bytes per
        element). Under tensor parallelism each device holds
        ``num_heads / tp_degree`` heads of every row (ISSUE 15), so this
        is the number a device's HBM budget divides by — capacity scales
        with the mesh."""
        if self.groups:
            # what GROWS with the context: the paged groups without a
            # window
            return sum(g.layers * g.row_bytes(self.dtype)
                       for g in self._paged_groups() if not g.ring)
        if not self.kv_pools:
            return self.num_layers * jnp.dtype(self.dtype).itemsize * sum(
                int(np.prod(r)) for r in self.row_shapes.values())
        if self.quantized:
            per_head = self.head_dim * 1 + 4          # int8 + f32 scale
        else:
            per_head = self.head_dim * jnp.dtype(self.dtype).itemsize
        heads_local = self.num_heads // self.tp_degree
        return 2 * self.num_layers * heads_local * per_head

    @property
    def bytes_per_block(self) -> int:
        """HBM bytes one pool block costs PER SHARD (both pools, scales
        included) — the equal-pool-bytes denominator the quantization
        and tensor-parallel bench legs size with."""
        return self.kv_bytes_per_token * self.block_size

    def blocks_needed(self, length: int) -> int:
        return -(-length // self.block_size)          # ceil

    def group_facts(self) -> Dict[str, Dict[str, int]]:
        """Each declared group's account: its layers and window, the bytes
        of one of its blocks (all its pools, all its layers), the blocks
        it has, those IN USE now (allocated, of a group that grows; of a
        ring the blocks the slots' lengths reach, ``ring`` a slot at
        most) and those LIVE (the blocks the slots' lengths reach, in
        either: what holds a position, without the reservation for
        positions to come), beside the positions themselves. Nothing for
        a cache without groups; a state group is :meth:`state_facts`'s."""
        reach = -(-self.lengths.astype(np.int64) // self.block_size)
        facts = {}
        for g in self._paged_groups():
            live = int(np.minimum(reach, g.ring).sum() if g.ring
                       else reach.sum())
            facts[g.name] = {
                "layers": g.layers, "window": g.window or 0,
                "block_bytes": g.layers * g.row_bytes(self.dtype)
                * self.block_size,
                "num_blocks": g.num_blocks, "blocks_live": live,
                "blocks_in_use": live if g.ring else
                self.num_blocks - 1 - self.allocator.reclaimable,
                "live_tokens": int(self.lengths.sum())}
        return facts

    def _paged_groups(self) -> List[PoolGroup]:
        return [g for g in self.groups.values() if not g.state]

    def state_facts(self) -> Dict[str, Dict[str, int]]:
        """Each STATE group's account: its layers, the bytes one slot's
        state takes in all of them and the bytes of the whole group."""
        return {g.name: {"layers": g.layers,
                         "slot_bytes": g.layers * g.row_bytes(self.dtype),
                         "bytes": g.layers * g.row_bytes(self.dtype)
                         * self.max_slots}
                for g in self.groups.values() if g.state}

    def owned_count(self, slot: int) -> int:
        """How many pool blocks ``slot`` currently holds — the size of
        the reservation an eviction returns to the free list (leak
        accounting for the eviction telemetry and tests)."""
        return len(self._owned[slot])

    # -- slot lifecycle ----------------------------------------------------

    def ensure_capacity(self, slot: int, new_len: int) -> bool:
        """Grow ``slot``'s block table to cover ``new_len`` tokens.
        Returns False (and changes nothing) if the pool cannot supply the
        extra blocks — the scheduler's backpressure signal."""
        assert new_len <= self.context_width, \
            f"length {new_len} exceeds slot capacity {self.context_width}"
        need = self.blocks_needed(new_len) - len(self._owned[slot])
        if need <= 0:
            return True
        got = self.allocator.alloc(need)
        if got is None:
            return False
        start = len(self._owned[slot])
        self._owned[slot].extend(got)
        self.tables[slot, start:start + len(got)] = got
        return True

    def free_slot(self, slot: int) -> None:
        """Decref ``slot``'s blocks (a shared block survives while other
        sequences still reference it; the LAST owner's decref frees it —
        or RETAINS it when its content is prefix-registered — ) and
        clear the table row. Decrefs run in REVERSE table order so a
        retained prefix chain's tail blocks are older in the LRU than
        its roots: reclaim-under-pressure eats tails first and a
        surviving partial chain still matches from the root. The pool
        data itself is NOT zeroed — stale block contents are finite and
        always masked by length, so reuse is a table update, not a
        memory wipe (the paged design's whole point)."""
        for b in reversed(self._owned[slot]):
            self._decref(b)
        if self._fork_reserve[slot] is not None:
            self._decref(self._fork_reserve[slot])
            self._fork_reserve[slot] = None
        self._owned[slot] = []
        self._adopted[slot] = set()
        self.tables[slot] = NULL_BLOCK
        self.lengths[slot] = 0

    def _decref(self, block: int) -> bool:
        retain = (self.retain_prefix and self.prefix_cache is not None
                  and self.prefix_cache.covers(block))
        dropped = self.allocator.decref(block, retain=retain)
        if dropped and not retain and self.prefix_cache is not None:
            self.prefix_cache.invalidate_block(block)
        return dropped

    # -- copy-on-write prefix sharing --------------------------------------
    #
    # Ownership discipline: the slot that ALLOCATED a block is its
    # writer and appends in place; a slot that ADOPTED a block via the
    # prefix cache reads rows below the registered coverage and must
    # FORK before its first write into it (``cow_targets`` names the
    # blocks, ``fork_block`` swaps them). Only the partial boundary
    # block of an exact-duplicate prompt is ever in that position —
    # adopted FULL blocks cover prompt positions strictly below the
    # sharer's write range — so admission reserves exactly one fork
    # block when the match includes a partial boundary.

    def match_prefix(self, tokens: List[int]) -> Optional[PrefixMatch]:
        """The resident shared prefix of ``tokens`` (None with sharing
        off, an empty match when nothing resident matches)."""
        if self.prefix_cache is None:
            return None
        return self.prefix_cache.match(tokens)

    def adopt_prefix(self, slot: int, match: PrefixMatch) -> None:
        """Map ``match``'s physical blocks into ``slot``'s table by
        reference (incref each) — the admission-side half of sharing.
        Must run on an empty slot, before ``ensure_capacity`` sizes the
        fresh-tail allocation. A partial boundary match also reserves
        the copy-on-write fork target so the first divergent write can
        never strand on an exhausted pool."""
        assert not self._owned[slot], "adopt_prefix on a non-empty slot"
        for i, b in enumerate(match.blocks):
            if self.allocator.incref(b):      # revived out of the LRU
                self.retained_hits += 1
            self.tables[slot, i] = b
        self._owned[slot] = list(match.blocks)
        self._adopted[slot] = set(range(len(match.blocks)))
        self.prefix_hit_blocks += len(match.blocks)
        if match.partial:
            got = self.allocator.alloc(1)
            if got is None:
                self.free_slot(slot)       # roll back the adoption
                raise RuntimeError(
                    f"KV pool exhausted reserving the COW fork block for "
                    f"slot {slot} — gate admissions on can_admit()")
            self._fork_reserve[slot] = got[0]

    def register_prefix(self, slot: int, tokens: List[int]) -> int:
        """Publish ``slot``'s freshly-written prompt blocks to the
        prefix cache (no-op with sharing off)."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.register(tokens, self._owned[slot])

    # -- cross-replica handoff (ISSUE 18) ----------------------------------

    def export_pages(self, slot: int):
        """Read out the pool pages covering ``slot``'s current length
        for a prefill→decode handoff: returns ``(block_ids, kpages,
        vpages)`` where the page arrays are host numpy ``[L, nb, H, bs,
        hd]`` (plus ``[L, nb, H, bs]`` scale leaves as ``(values,
        scales)`` tuples when quantized) in TABLE ORDER — physical block
        ids don't travel; the receiver re-homes the pages at its own
        allocations. Shared/adopted blocks export fine (it's a read);
        only blocks covering the length ship, not the reservation."""
        self._kv_only("export_pages")
        nb = self.blocks_needed(int(self.lengths[slot]))
        ids = [int(b) for b in self.tables[slot, :nb]]
        sel = jnp.asarray(ids, jnp.int32)

        def take(pool):
            if isinstance(pool, tuple):
                return (np.asarray(pool[0][:, sel]),
                        np.asarray(pool[1][:, sel]))
            return np.asarray(pool[:, sel])

        return ids, take(self.k), take(self.v)

    def import_pages(self, slot: int, kpages, vpages, length: int,
                     reserve_len: Optional[int] = None) -> bool:
        """Adopt handed-off pages into an EMPTY slot: allocate blocks to
        cover ``max(length, reserve_len)`` (the full decode reservation,
        so adoption can never strand mid-sequence on a dry pool), write
        the page bytes at this pool's own block ids, and set the length.
        Returns False (nothing changed) when the pool can't supply the
        blocks — the decode side's backpressure; the fleet retries or
        re-routes the handoff."""
        self._kv_only("import_pages")
        assert not self._owned[slot], "import_pages on a non-empty slot"
        target = max(int(length), int(reserve_len or 0))
        if not self.ensure_capacity(slot, target):
            return False
        nb = self.blocks_needed(int(length))
        sel = jnp.asarray(self._owned[slot][:nb], jnp.int32)

        def put(pool, pages):
            if isinstance(pool, tuple):
                return (pool[0].at[:, sel].set(
                            jnp.asarray(pages[0], pool[0].dtype)),
                        pool[1].at[:, sel].set(
                            jnp.asarray(pages[1], pool[1].dtype)))
            return pool.at[:, sel].set(jnp.asarray(pages, pool.dtype))

        self.k = put(self.k, kpages)
        self.v = put(self.v, vpages)
        self.lengths[slot] = int(length)
        return True

    def cow_targets(self, slot: int, lo: int, hi: int) -> List[int]:
        """Table indices of ``slot``'s ADOPTED, still multiply-owned
        blocks covering write positions ``[lo, hi]`` — the engine forks
        exactly these before a tick scatters there. An adopted block
        whose co-owners all evicted promotes to write-in-place (its
        cache coverage is below every write this slot will ever do)."""
        bs = self.block_size
        out = []
        for i in sorted(self._adopted[slot]):
            if not lo // bs <= i <= hi // bs:
                continue
            if self.allocator.ref_count(self._owned[slot][i]) > 1:
                out.append(i)
            else:
                self._adopted[slot].discard(i)
        return out

    def fork_block(self, slot: int, index: int) -> Tuple[int, int]:
        """Copy-on-write fork of ``slot``'s table entry ``index``:
        point the slot at the admission-reserved fork target (or a
        fresh allocation) and decref the shared original. Returns
        ``(src, dst)`` — the CALLER owns the device copy of the pool
        pages (host tables know nothing about HBM)."""
        src = self._owned[slot][index]
        dst = self._fork_reserve[slot]
        if dst is None:
            got = self.allocator.alloc(1)
            if got is None:
                raise RuntimeError(
                    f"KV pool exhausted forking shared block {src} for "
                    f"slot {slot} — admission must reserve fork headroom")
            dst = got[0]
        else:
            self._fork_reserve[slot] = None
        self._owned[slot][index] = dst
        self.tables[slot, index] = dst
        self._adopted[slot].discard(index)
        self._decref(src)
        self.cow_forks += 1
        return src, dst

    def shard_pools(self, mesh, axis: str = "model") -> None:
        """Commit the device pools head-sharded over ``mesh``'s ``axis``
        (ISSUE 15): values ``[L, N, H, bs, hd]`` split on the H axis,
        int8 scale pages ``[L, N, H, bs]`` split identically, so every
        shard owns its head group of EVERY block. The host side — tables,
        lengths, allocator, prefix cache — is untouched and stays
        shard-oblivious: admission/eviction/CoW/retention reason about
        one logical block table while the bytes live distributed."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._kv_only("shard_pools")
        # TRIMMED spec (no trailing None): matches the normalized form
        # `tp_constrain` pins on the compiled programs' pool outputs, so
        # the carry's sharding hashes identical call to call (padded vs
        # trimmed specs retrace on some jax versions). Covers both the
        # [L, N, H, bs, hd] value pages and [L, N, H, bs] scale pages.
        sh = NamedSharding(mesh, P(None, None, axis))

        def put(pool):
            if isinstance(pool, tuple):
                return (jax.device_put(pool[0], sh),
                        jax.device_put(pool[1], sh))
            return jax.device_put(pool, sh)

        self.k = put(self.k)
        self.v = put(self.v)

    def device_tables(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The current (tables, lengths) as device operands for a tick,
        made from numpy COPIES that nobody else holds: the engine bumps
        ``lengths`` in place while the tick that reads them is still in
        flight, and neither ``jnp.asarray`` nor ``jnp.array`` of the live
        array is safe against that. ``jnp.asarray`` may alias a numpy
        buffer zero-copy; ``jnp.array`` copies, but under asynchronous
        dispatch the copy is made when the transfer runs, not when the
        call returns, so with a prefill chunk still in flight ahead of it
        the tick read lengths one too long (measured on the CPU backend,
        PR 29: 17 of 40 runs of one scenario served another token; none
        of 40 with the copies taken here)."""
        return (self._tables(jnp.asarray(self.tables.copy()),
                             self._ring_device, self._first_slot),
                jnp.asarray(self.lengths.copy()))

    def _tables(self, grown, rings, first):
        """``grown``, the table of the account that grows, as the entry
        points take it: itself, or with declared groups ``{group: table}``
        (a window group's is its ring's, which never changes; a state
        group's the scalar ``first`` slot of the call's rows, which are
        consecutive slots)."""
        if not self.groups:
            return grown
        return {g.name: first if g.state else rings[g.name] if g.ring
                else grown for g in self.groups.values()}

    def slot_tables(self, slot: int):
        """One slot's ``[1, MB]`` table(s), a prefill chunk's operand."""
        rows = slice(slot, slot + 1)
        return self._tables(
            jnp.asarray(self.tables[rows]),
            {n: jnp.asarray(t[rows]) for n, t in self._ring_tables.items()},
            jnp.asarray(slot, jnp.int32))
