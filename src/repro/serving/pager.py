"""Device-side page allocator for the paged KV-cache layout.

The paper's central lesson is that performance portability comes from
hiding a data structure's *layout* behind one high-level abstraction so the
same calling code targets every backend.  The KV cache has two layouts
behind ``ops.attention_decode`` (the ``KVCacheLayout`` contract):

  contiguous   ``(layers, B, max_len, Hkv, hd)`` slab — token ``p`` of row
               ``b`` lives at ``cache[:, b, p]`` (ring-indexed by
               ``p % window`` for sliding-window archs).  Memory is
               ``B x max_len`` regardless of actual prompt lengths.
  paged        a pool of fixed-size pages plus a per-row block table.
               Memory scales with *live tokens*, not ``B x max_len``.

Block-table layout contract (shared by the jnp reference path and
``flash_decode_paged_pallas`` — keep them in lock-step):

  * page pool      ``(layers, n_pages, page_size, Hkv, hd)`` — one slab per
    layer (or per shared-attention group for the hybrid family), all slabs
    indexed by the same page ids.
  * block table    ``(B, max_blocks)`` int32.  Token at absolute position
    ``p`` of row ``b`` lives in page ``block_table[b, p // page_size]`` at
    slot ``p % page_size``.  ``-1`` marks an unmapped block; readers must
    treat unmapped blocks as fully masked and writers must drop the write.
  * positions are *absolute* (no ring): sliding-window archs mask old
    tokens in attention instead of recycling slots, so a live windowed row
    does not release pages mid-request (documented trade-off — the win is
    cross-request reuse, which dominates at mixed prompt lengths).
  * freed pages are recycled **without zeroing**: a new owner writes
    positions ``0..pos`` sequentially before any read at ``kpos < pos+1``
    can see them, so stale data is never observable.

Allocator state is three device arrays (the free list as a stack plus a
per-page refcount), so allocation, sharing and release are pure ``jnp``
and run *inside* jitted steps with fixed shapes — the same masked-write
idiom as the serving engine's slot refill; nothing retraces:

  * ``free``  ``(n_pages,)`` int32 — entries ``[0, top)`` are free page
    ids; entries above ``top`` are stale (owned by block tables).
  * ``top``   ``()`` int32 — number of free pages.
  * ``rc``    ``(n_pages,)`` int32 — per-page refcount: how many
    block-table entries reference the page.  0 for free pages.

``alloc_on_write`` maps the block a row is about to write (pop from the
stack top; rows ranked by batch index within one step) and sets its
refcount to 1; ``release_rows`` decrements every mapped page of the
released rows and pushes only the pages whose refcount reaches 0 back
onto the stack.  Conservation invariant (the hypothesis property in
``tests/test_pager.py``): the free-list prefix and the pages referenced
by block tables always partition ``0..n_pages-1``, and each referenced
page's refcount equals the number of block-table entries pointing at it
— no page is simultaneously free and mapped, or lost.

Prefix sharing and copy-on-write (the refcount's reason to exist):

  * ``share_prefix`` maps the leading blocks of a *donor* row into a
    newly admitted row's block table and bumps each shared page's
    refcount — the sharer reads the donor's already-written prompt K/V
    without re-running prefill for it.  Shared pages are always *full*
    prompt pages (page-aligned sharing), so the donor never writes them
    again (its write positions only grow).
  * a page with ``rc > 1`` is read-only to everyone.  ``cow_on_write``
    runs before any paged write: a row about to write a page it does not
    exclusively own pops a fresh page, swaps its block-table entry, and
    drops its ref on the shared page (a ref dropped to 0 — every other
    holder CoW'd or released first — sends the page straight back to the
    free list, so simultaneous CoWs cannot leak it).  The caller copies
    the already-written slot prefix with ``copy_page_prefix`` (a jitted
    masked copy — slots at and above the write position are garbage by
    construction and are zeroed, never read).  Because sharing is
    page-aligned, a row can hit CoW at most once: only when its whole
    prompt is shared (the re-fed last prompt token lands in the final
    shared page); engine admission reserves one extra page for it.
  * pop order within one jitted step is deterministic: CoW pops rank
    before the step's ``alloc_on_write``/``alloc_range`` pops, rows
    ranked by batch index inside each — the engine's host mirror relies
    on nothing finer than the reservation totals, but tests do.

Recurrent-state snapshot slots (the same machinery, one level up): the
recurrent families (ssm / hybrid) cannot *skip* prompt positions the way
attention can read a peer's pages — the state at token t depends on every
token before it.  What they can do is *restore*: the decode state grows a
page-boundary snapshot store whose contract deliberately mirrors the
block table's (``lm.init_decode_state(snapshots=True)`` builds it):

  * snapshot pools ``snap_ssm (n_slots, layers, H, P, N)`` f32 and
    ``snap_conv (n_slots, layers, K-1, d_inner + 2*N)`` (the conv window
    over x|B|C) — one slot holds a row's *full-depth* SSM + conv state
    captured exactly at a page boundary (after feeding ``(j+1) *
    page_size`` tokens).
  * slot table  ``snap_table (B, max_boundaries)`` int32 — column ``j``
    maps the slot for boundary ``j+1``; ``-1`` = no snapshot.  Boundary
    space is block space with ``page_size == 1``: the *same* allocator
    functions (``alloc_on_write`` / ``share_prefix`` / ``release_rows``)
    manage slots, so the free list / refcount conservation invariant —
    and its property test — carry over verbatim.
  * capture      a step that *ends* exactly at a boundary allocates the
    column's slot and scatters the post-step state into the pools
    (``lm._snap_capture``); the serving engine clips chunk widths so
    every boundary is a step endpoint.  A slot with rc > 1 is read-only
    (the shared-page contract); slots are recycled without zeroing — a
    recycled slot is fully overwritten at its next capture before any
    restore can read it.
  * share/restore  admission maps the donor's leading ``nblk`` slots
    (refcount bumps keep them alive past the donor's release, exactly
    like shared pages) and loads slot ``nblk - 1`` into the row's live
    state (``lm.restore_snapshots``), so prefill resumes at the first
    unshared token with the recurrence already advanced.
  * release      ``reset_decode_rows`` releases a row's slots with its
    pages: refs drop, rc==0 slots return to the free stack, slots still
    held by a sharer stay resident.
  * sizing       the slot pool is built at the worst case
    (``batch x ceil(max_len / page_size)``) so — like the engine's page
    reservation ledger — capture can never find the free list dry.

Two-tier paging — host spill / restore (the memory-pressure escape
valve): the engine's preemption path moves a victim row's pages out of
the device pool instead of dropping the request.  The host tier is a
*second* (pool, table, free list, refcount) quadruple managed by the
same allocator primitives, so the conservation invariant generalizes
instead of forking:

  * ``spill_rows`` pops one host slot per mapped block of each victim
    row, records it in the row's host table, and *releases* the device
    pages (``release_rows`` — a page still referenced by a
    prefix-sharing peer stays resident; the victim gets a private host
    copy either way).  The returned ``(src, dst)`` id vectors drive the
    data move (``copy_pages``) device-pool → host-pool inside the same
    jitted call — release never zeroes pool data, so copying after the
    bookkeeping is safe.
  * ``restore_rows`` is the exact mirror: pop fresh device pages for
    every host-table entry, copy host-pool → device-pool, release the
    host slots.  A restored row owns its pages privately (rc == 1) even
    where it used to share — sharing is re-established only through
    future admissions, never assumed across a spill.
  * sizing & dryness: the host pool is built at the worst case
    (``batch x max_blocks`` slots — every row fully resident, all
    spilled), so a spill can never find the host free list dry; restore
    pops are gated by the engine's reservation ledger (the row's
    worst-case page count re-enters the ledger before ``restore_rows``
    runs), so they can never find the *device* free list dry.  Both are
    the same "reservation prevents this" convention as ``alloc_on_write``
    — a dry pop degrades to a skipped block, never to corruption.
  * conservation (the generalized property in ``tests/test_pager.py``):
    within each tier, the free-list prefix and the pages referenced by
    that tier's tables partition ``0..n-1`` and rc equals reference
    multiplicity — "free + device-resident" and "free + host-resident"
    each partition their pool, with host rc always 1 (host copies are
    private by construction).
  * snapshot slots ride the same functions: for recurrent families the
    engine spills the victim's snapshot table through ``spill_rows`` on
    boundary space against a host snapshot pool (``copy_pages`` with
    ``axis=0`` — snapshot pools are slot-major), so shared boundary
    state survives the victim's eviction exactly like shared KV pages.
  * placement note: in this repro the host pools are ordinary arrays —
    the two-tier *accounting* is the contract.  On a real TPU they would
    be pinned-host buffers (``memory_kind="pinned_host"``); nothing in
    the bookkeeping changes.

Quantized pools (``CacheConfig(kv_dtype="int8")``): the KV payload is
stored as symmetric int8 (``q = clip(round(x / s), -127, 127)``) with
one f32 scale per (page, kv-head) in a companion *scale pool* —
``(stacks, n_pages, Hkv)`` beside the ``(stacks, n_pages, page_size,
Hkv, hd)`` payload, ``(stacks, n_slots, Hkv)`` on the host tier.  The
scale is the page's running amax over its written slots divided by 127:
``write_page_quant`` / ``write_page_chunk_quant`` *reset* it when they
write slot 0 of a page (sequential writes enter every fresh page at
slot 0, so a recycled page's stale payload+scale never leak into a new
owner) and otherwise merge by max, requantizing the already-written
payload in the same scatter when the scale grows.  Scales are
page-indexed bookkeeping exactly like refcounts:

  * spill/restore — the scale pools ride the same ``(src, dst)`` id
    vectors through ``copy_pages`` (page axis 1, like the payload), so
    the host tier stores the *quantized* form and spill bandwidth
    halves along with residency;
  * CoW — ``copy_page_scale`` moves the donor page's scales onto the
    fresh page alongside ``copy_page_prefix``, so the copied slot
    prefix keeps dequantizing bit-identically;
  * share/release — no scale work: scales travel with the page id, and
    the slot-0 reset on the next owner's first write retires stale
    entries.

Attention accumulation is unaffected: the payload dequantizes to f32
inside the kernels (``flash_*_paged_quant_pallas``) and the scale pools
themselves stay f32 end to end (lint rule R007).

Multi-page-per-step allocation (chunked prefill): a step that writes a
*range* of positions ``start..end`` may straddle several blocks, so
``alloc_range`` maps every block covering the range in one jitted call —
a statically unrolled ladder of single-block ``alloc_on_write`` passes
(``(max_chunk-1)//page_size + 2`` of them), each with the same
rank-by-batch-index pop order, so the conservation invariant and the
fixed-shape/no-retrace discipline are unchanged.  ``write_page_chunk`` is
the matching multi-token scatter: token ``i`` of row ``b`` lands at
``(block_table[b, (start+i)//page_size], (start+i) % page_size)``; chunk
padding (``i >= width``) and inactive rows route to the out-of-bounds
sentinel page and drop.  Admission-time reservation already covers the
worst case (``pages_needed`` counts positions ``0..total_len-2``), so a
chunked step can never find the free list empty for a live request.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def _kv_pool(fn):
    """Stage every device op of ``fn`` under the ``kv_pool`` name scope,
    so the compiled program's op metadata (and a device trace) names the
    pool's allocator, copy and write work wherever it is called from."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("kv_pool"):
            return fn(*args, **kwargs)
    return scoped


class PagerState(NamedTuple):
    """Free-list stack + per-page refcounts as device arrays (a pytree;
    jit/donation friendly)."""

    free: jax.Array  # (n_pages,) int32: free[:top] are free page ids
    top: jax.Array   # ()        int32: number of free pages
    rc: jax.Array    # (n_pages,) int32: block-table refs per page (0 = free)


def init_pager(n_pages: int) -> PagerState:
    return PagerState(
        free=jnp.arange(n_pages, dtype=jnp.int32),
        top=jnp.asarray(n_pages, jnp.int32),
        rc=jnp.zeros((n_pages,), jnp.int32),
    )


def _push_freed(free: jax.Array, top: jax.Array,
                freed: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Push the pages selected by the (n_pages,) bool mask onto the stack
    (ascending page id — any deterministic order works; readers only ever
    pop from the top)."""
    n_pages = free.shape[0]
    page_ids = jnp.arange(n_pages, dtype=jnp.int32)
    rank = jnp.cumsum(freed) - 1
    dst = jnp.where(freed, top + rank, n_pages)       # sentinel: dropped
    free = free.at[dst].set(page_ids, mode="drop")
    return free, top + jnp.sum(freed, dtype=jnp.int32)


def init_block_table(batch: int, max_blocks: int) -> jax.Array:
    return jnp.full((batch, max_blocks), -1, jnp.int32)


def pages_needed(total_len: int, page_size: int) -> int:
    """Pages a request reserves at admission (host-side accounting).

    A request of ``total_len`` tokens writes cache positions
    ``0..total_len-2`` (the feed at the last position only *predicts*, its
    token is never cached), touching ``ceil((total_len-1)/page_size)``
    blocks.  Admission reserves this worst case so alloc-on-write can never
    find the free list empty mid-request.
    """
    return max(1, -(-(total_len - 1) // page_size))


@_kv_pool
def alloc_on_write(
    pager: PagerState,
    block_table: jax.Array,          # (B, max_blocks) int32
    idx: jax.Array,                  # () or (B,) int32: position being written
    active: Optional[jax.Array] = None,   # (B,) bool; None = all rows
    *,
    page_size: int,
) -> Tuple[PagerState, jax.Array]:
    """Map the block covering ``idx`` for every row that needs one.

    Pure ``jnp``, fixed shapes: rows needing a page are ranked by batch
    index and pop ``free[top-1-rank]``.  A row whose block is already
    mapped, out of range, or inactive is untouched; if the free list runs
    dry the remaining rows simply stay unmapped (writes to unmapped blocks
    drop — admission-time reservation prevents this for live requests).
    """
    b, max_blocks = block_table.shape
    idx_b = jnp.broadcast_to(jnp.asarray(idx, jnp.int32).reshape(-1), (b,))
    if active is None:
        active = jnp.ones((b,), bool)
    blk = idx_b // page_size
    in_range = blk < max_blocks
    blk_c = jnp.clip(blk, 0, max_blocks - 1)
    cur = jnp.take_along_axis(block_table, blk_c[:, None], axis=1)[:, 0]
    need = active & in_range & (cur < 0)
    rank = jnp.cumsum(need) - 1                     # rank among needy rows
    grant = need & (rank < pager.top)
    n_pages = pager.free.shape[0]
    src = jnp.clip(pager.top - 1 - rank, 0, n_pages - 1)
    page = jnp.where(grant, pager.free[src], cur)
    col = jax.lax.broadcasted_iota(jnp.int32, block_table.shape, 1)
    block_table = jnp.where(
        grant[:, None] & (col == blk_c[:, None]), page[:, None], block_table
    )
    top = pager.top - jnp.sum(grant, dtype=jnp.int32)
    rc = pager.rc.at[jnp.where(grant, page, n_pages)].set(1, mode="drop")
    return PagerState(pager.free, top, rc), block_table


@_kv_pool
def alloc_range(
    pager: PagerState,
    block_table: jax.Array,          # (B, max_blocks) int32
    start: jax.Array,                # () or (B,) int32: first position written
    end: jax.Array,                  # () or (B,) int32: last position written
    active: Optional[jax.Array] = None,   # (B,) bool; None = all rows
    *,
    page_size: int,
    max_chunk: int,
) -> Tuple[PagerState, jax.Array]:
    """Map every block covering positions ``start..end`` (inclusive).

    The multi-page-per-step generalization of ``alloc_on_write`` for
    chunked prefill: ``max_chunk`` statically bounds ``end - start + 1``,
    so the loop unrolls to a fixed ladder of single-block allocations
    (fixed shapes, nothing retraces).  Rung ``k`` targets block
    ``start//page_size + k`` and is masked out for rows whose range ends
    in an earlier block, so rows needing fewer blocks allocate fewer
    pages.  The gate compares *block indices*, not positions: a range
    starting mid-page (speculative-decoding verify chunks start at
    arbitrary positions) can cross into its next block fewer than
    ``page_size`` positions after ``start``, so gating on
    ``start + k*page_size <= end`` would skip a block the chunk writes.
    """
    b = block_table.shape[0]
    start_b = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (b,))
    end_b = jnp.broadcast_to(jnp.asarray(end, jnp.int32).reshape(-1), (b,))
    if active is None:
        active = jnp.ones((b,), bool)
    start_blk = start_b // page_size
    end_blk = end_b // page_size
    for k in range((max_chunk - 1) // page_size + 2):
        blk = start_blk + k
        # first position of block ``blk`` inside the range (== start for
        # the first rung, the block's base position after that)
        idx = jnp.maximum(start_b, blk * page_size)
        pager, block_table = alloc_on_write(
            pager, block_table, jnp.minimum(idx, end_b),
            active & (blk <= end_blk), page_size=page_size,
        )
    return pager, block_table


@_kv_pool
def release_rows(
    pager: PagerState,
    block_table: jax.Array,   # (B, max_blocks) int32
    mask: jax.Array,          # (B,) bool: rows whose pages return to the pool
) -> Tuple[PagerState, jax.Array]:
    """Drop the masked rows' refs on every page they map, push the pages
    whose refcount reaches 0 back onto the free stack, and unmap the rows.
    A page still referenced by a prefix-sharing peer stays resident (its
    content outlives the row that first wrote it).  Releasing an
    already-empty row is a no-op, so release-on-completion and
    release-at-admission compose."""
    n_pages = pager.free.shape[0]
    give = mask[:, None] & (block_table >= 0)
    pages = jnp.where(give, block_table, n_pages).reshape(-1)
    # per-page ref drops (duplicates accumulate: two released sharers of
    # one page decrement it twice in this single call)
    dec = jnp.zeros((n_pages,), jnp.int32).at[pages].add(1, mode="drop")
    rc = pager.rc - dec
    freed = (pager.rc > 0) & (rc <= 0) & (dec > 0)
    rc = jnp.maximum(rc, 0)
    free, top = _push_freed(pager.free, pager.top, freed)
    block_table = jnp.where(mask[:, None], -1, block_table)
    return PagerState(free, top, rc), block_table


@_kv_pool
def release_tail(
    pager: PagerState,
    block_table: jax.Array,   # (B, max_blocks) int32
    frontier: jax.Array,      # (B,) int32: highest live position + 1
    mask: jax.Array,          # (B,) bool: rows to roll back
    *,
    page_size: int,
) -> Tuple[PagerState, jax.Array]:
    """Release the masked rows' blocks strictly *beyond* their write
    frontier (speculative-decoding rollback).

    The verify step of draft-and-verify allocates pages for the full
    drafted chunk before knowing how much survives acceptance; a row
    that accepts fewer tokens keeps its blocks up to and including the
    one covering position ``frontier - 1`` (the last *written* cache
    position is ``frontier - 1`` — the feed at ``frontier`` only
    predicts) and returns the over-allocated tail to the pool.  Same
    refcount discipline as ``release_rows`` (the tail pages of a
    verify-chunk are freshly allocated and private, but the masked
    decrement keeps the conservation invariant unconditional), and a
    row whose tail is empty is a no-op — so calling it every spec step
    composes with release-on-completion."""
    n_pages = pager.free.shape[0]
    b, max_blocks = block_table.shape
    fr = jnp.broadcast_to(jnp.asarray(frontier, jnp.int32).reshape(-1), (b,))
    keep_blk = (jnp.maximum(fr, 1) - 1) // page_size
    col = jax.lax.broadcasted_iota(jnp.int32, block_table.shape, 1)
    give = (mask[:, None] & (block_table >= 0)
            & (col > keep_blk[:, None]))
    pages = jnp.where(give, block_table, n_pages).reshape(-1)
    dec = jnp.zeros((n_pages,), jnp.int32).at[pages].add(1, mode="drop")
    rc = pager.rc - dec
    freed = (pager.rc > 0) & (rc <= 0) & (dec > 0)
    rc = jnp.maximum(rc, 0)
    free, top = _push_freed(pager.free, pager.top, freed)
    block_table = jnp.where(give, -1, block_table)
    return PagerState(free, top, rc), block_table


@_kv_pool
def spill_rows(
    pager: PagerState,
    table: jax.Array,         # (B, max_blocks) int32: device-tier table
    hpager: PagerState,       # host-tier allocator (n_slots entries)
    htable: jax.Array,        # (B, max_blocks) int32: host-tier table
    mask: jax.Array,          # (B,) bool: victim rows
) -> Tuple[PagerState, jax.Array, PagerState, jax.Array, jax.Array, jax.Array]:
    """Move the masked rows' mapped blocks from the device tier to the
    host tier (preemption).

    For every mapped block of a victim row: pop a host slot (rows and
    blocks ranked in flattened row-major order — the same deterministic
    pop discipline as ``alloc_on_write``), record it in the host table,
    then release the device pages (``release_rows`` — a page a
    prefix-sharing peer still references stays resident; the victim gets
    a private host copy regardless, so restore never depends on the peer
    outliving the spill).

    Returns ``(pager, table, hpager, htable, src, dst)``.  ``src`` /
    ``dst`` are flattened ``(B * max_blocks,)`` id vectors — device page
    to read, host slot to fill — with out-of-bounds sentinels for
    blocks that did not move; feed them to ``copy_pages`` *in the same
    jitted call* (release touches only bookkeeping, never pool data, so
    copying after the release is safe).  Host-pool dryness is prevented
    by worst-case sizing (see the module docstring); a dry pop skips the
    block, never corrupts."""
    b, max_blocks = table.shape
    n_pages = pager.free.shape[0]
    n_slots = hpager.free.shape[0]
    give = mask[:, None] & (table >= 0) & (htable < 0)
    flat = give.reshape(-1)
    rank = jnp.cumsum(flat) - 1
    grant = flat & (rank < hpager.top)
    sidx = jnp.clip(hpager.top - 1 - rank, 0, n_slots - 1)
    slot = jnp.where(grant, hpager.free[sidx], n_slots)
    h_top = hpager.top - jnp.sum(grant, dtype=jnp.int32)
    h_rc = hpager.rc.at[slot].set(1, mode="drop")   # host copies are private
    htable = jnp.where(
        grant.reshape(b, max_blocks), slot.reshape(b, max_blocks), htable
    )
    src = jnp.where(grant, table.reshape(-1), n_pages)
    dst = slot
    pager, table = release_rows(pager, table, mask)
    return pager, table, PagerState(hpager.free, h_top, h_rc), htable, src, dst


@_kv_pool
def restore_rows(
    pager: PagerState,
    table: jax.Array,         # (B, max_blocks) int32: device-tier table
    hpager: PagerState,       # host-tier allocator (n_slots entries)
    htable: jax.Array,        # (B, max_blocks) int32: host-tier table
    mask: jax.Array,          # (B,) bool: rows to bring back on device
) -> Tuple[PagerState, jax.Array, PagerState, jax.Array, jax.Array, jax.Array]:
    """The exact mirror of ``spill_rows``: re-allocate device pages for
    every host-table entry of the masked rows, then release the host
    slots (always rc == 1 — host copies are private by construction).

    A restored row owns its pages privately (rc == 1) even where it used
    to share.  Returns ``(pager, table, hpager, htable, src, dst)`` with
    ``src`` = host slots to read, ``dst`` = fresh device pages to fill
    (flattened, sentinel = skip) for ``copy_pages``.  Device-pool
    dryness is prevented by the engine's reservation ledger (the row's
    worst-case page count re-enters the ledger before this runs)."""
    b, max_blocks = table.shape
    n_pages = pager.free.shape[0]
    n_slots = hpager.free.shape[0]
    take = mask[:, None] & (htable >= 0) & (table < 0)
    flat = take.reshape(-1)
    rank = jnp.cumsum(flat) - 1
    grant = flat & (rank < pager.top)
    pidx = jnp.clip(pager.top - 1 - rank, 0, n_pages - 1)
    page = jnp.where(grant, pager.free[pidx], n_pages)
    top = pager.top - jnp.sum(grant, dtype=jnp.int32)
    rc = pager.rc.at[page].set(1, mode="drop")
    table = jnp.where(
        grant.reshape(b, max_blocks), page.reshape(b, max_blocks), table
    )
    src = jnp.where(grant, htable.reshape(-1), n_slots)
    dst = page
    hpager, htable = release_rows(hpager, htable, mask)
    return PagerState(pager.free, top, rc), table, hpager, htable, src, dst


@_kv_pool
def copy_pages(
    dst_pool: jax.Array,
    src_pool: jax.Array,
    src: jax.Array,     # (M,) int32 ids into src_pool's page axis
    dst: jax.Array,     # (M,) int32 ids into dst_pool's page axis
    *,
    axis: int = 1,
) -> jax.Array:
    """Bulk whole-page move between pools (the spill/restore data plane).

    Gathers page ``src[i]`` from ``src_pool`` and scatters it to page
    ``dst[i]`` of ``dst_pool``; out-of-bounds sentinels drop.  ``axis``
    selects the page axis: 1 for KV pools (``(stacks, n_pages, ...)``),
    0 for snapshot pools (slot-major ``(n_slots, ...)``).  Whole pages
    are copied — slots beyond the written prefix carry garbage on both
    sides of the move, which the sequential-write contract already makes
    unobservable."""
    n_src = src_pool.shape[axis]
    content = jnp.take(src_pool, jnp.clip(src, 0, n_src - 1), axis=axis)
    content = content.astype(dst_pool.dtype)
    if axis == 0:
        return dst_pool.at[dst].set(content, mode="drop")
    if axis == 1:
        return dst_pool.at[:, dst].set(content, mode="drop")
    raise ValueError(f"copy_pages: unsupported page axis {axis}")


@_kv_pool
def share_prefix(
    pager: PagerState,
    block_table: jax.Array,   # (B, max_blocks) int32
    src: jax.Array,           # (B,) int32: donor row per admitted row
    nblk: jax.Array,          # (B,) int32: leading blocks to share (0 = none)
    mask: jax.Array,          # (B,) bool: rows being admitted
) -> Tuple[PagerState, jax.Array]:
    """Map the donor rows' leading blocks into the masked rows and bump the
    shared pages' refcounts.

    Pure ``jnp``, fixed shapes — runs inside the engine's jitted ``_admit``
    (``nblk == 0`` rows are untouched, so the non-sharing admission path is
    the same trace).  The caller (the engine's host-side prefix index)
    guarantees the donor is a live row outside ``mask`` whose first
    ``nblk`` blocks are mapped and fully written; unmapped donor entries
    are skipped defensively.  Duplicate bumps accumulate: two rows
    admitted in one call sharing the same donor page raise its refcount
    by two."""
    b = block_table.shape[0]
    n_pages = pager.free.shape[0]
    src_c = jnp.clip(jnp.asarray(src, jnp.int32).reshape(-1), 0, b - 1)
    donor = block_table[src_c]                          # (B, max_blocks)
    col = jax.lax.broadcasted_iota(jnp.int32, block_table.shape, 1)
    nblk_b = jnp.broadcast_to(jnp.asarray(nblk, jnp.int32).reshape(-1), (b,))
    take = mask[:, None] & (col < nblk_b[:, None]) & (donor >= 0)
    block_table = jnp.where(take, donor, block_table)
    pages = jnp.where(take, donor, n_pages).reshape(-1)
    inc = jnp.zeros((n_pages,), jnp.int32).at[pages].add(1, mode="drop")
    return PagerState(pager.free, pager.top, pager.rc + inc), block_table


@_kv_pool
def cow_on_write(
    pager: PagerState,
    block_table: jax.Array,          # (B, max_blocks) int32
    idx: jax.Array,                  # () or (B,) int32: position being written
    active: Optional[jax.Array] = None,   # (B,) bool; None = all rows
    *,
    page_size: int,
) -> Tuple[PagerState, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Copy-on-write step: un-share the page each row is about to write.

    For every active row whose target block maps a page with ``rc > 1``
    (readable by someone else), pop a fresh page, swap the block-table
    entry, set the fresh page's refcount to 1 and drop the row's ref on
    the shared page — pages whose refcount reaches 0 (simultaneous CoWs
    by every remaining holder) go straight back to the free list, so
    nothing leaks.  Returns ``(pager, block_table, src, dst, limit,
    moved)``: the caller must copy slots ``[0, limit)`` of each moved
    row's old page into the new one in every pool slab
    (``copy_page_prefix``) *before* writing position ``idx``.  ``src`` /
    ``dst`` are ``n_pages`` sentinels for rows that did not move.

    Rows needing a fresh page rank by batch index, same pop discipline as
    ``alloc_on_write``; admission-time reservation (one spare page per
    fully-shared prompt) keeps the free list from running dry here, and a
    dry pop leaves the row on the shared page (same "reservation prevents
    this" convention as a denied alloc)."""
    b, max_blocks = block_table.shape
    n_pages = pager.free.shape[0]
    idx_b = jnp.broadcast_to(jnp.asarray(idx, jnp.int32).reshape(-1), (b,))
    if active is None:
        active = jnp.ones((b,), bool)
    blk = idx_b // page_size
    blk_c = jnp.clip(blk, 0, max_blocks - 1)
    cur = jnp.take_along_axis(block_table, blk_c[:, None], axis=1)[:, 0]
    shared = (
        active & (blk < max_blocks) & (cur >= 0)
        & (pager.rc[jnp.clip(cur, 0, n_pages - 1)] > 1)
    )
    rank = jnp.cumsum(shared) - 1
    grant = shared & (rank < pager.top)
    slot = jnp.clip(pager.top - 1 - rank, 0, n_pages - 1)
    fresh = jnp.where(grant, pager.free[slot], cur)
    col = jax.lax.broadcasted_iota(jnp.int32, block_table.shape, 1)
    block_table = jnp.where(
        grant[:, None] & (col == blk_c[:, None]), fresh[:, None], block_table
    )
    top = pager.top - jnp.sum(grant, dtype=jnp.int32)
    old = jnp.where(grant, cur, n_pages)
    dec = jnp.zeros((n_pages,), jnp.int32).at[old].add(1, mode="drop")
    rc = pager.rc - dec
    orphaned = (pager.rc > 0) & (rc <= 0) & (dec > 0)
    rc = jnp.maximum(rc, 0)
    rc = rc.at[jnp.where(grant, fresh, n_pages)].set(1, mode="drop")
    free, top = _push_freed(pager.free, top, orphaned)
    limit = idx_b % page_size
    dst = jnp.where(grant, fresh, n_pages)
    return PagerState(free, top, rc), block_table, old, dst, limit, grant


@_kv_pool
def copy_page_prefix(
    pool: jax.Array,    # (stacks, n_pages, page_size, Hkv, hd)
    src: jax.Array,     # (B,) int32 page ids (n_pages sentinel = skip row)
    dst: jax.Array,     # (B,) int32 page ids (n_pages sentinel = skip row)
    limit: jax.Array,   # (B,) int32: copy slots [0, limit)
) -> jax.Array:
    """The CoW data move: copy each moved row's already-written slot
    prefix from its old page to its fresh page across every layer slab in
    one masked gather/scatter.  Slots at and above ``limit`` hold garbage
    by the sequential-write contract and are zeroed, never read."""
    n_pages, page_size = pool.shape[1], pool.shape[2]
    content = pool[:, jnp.clip(src, 0, n_pages - 1)]   # (stacks, B, S, ...)
    keep = jnp.arange(page_size, dtype=jnp.int32)[None, :] < limit[:, None]
    content = jnp.where(
        keep[None, :, :, None, None], content, jnp.zeros((), pool.dtype)
    )
    return pool.at[:, dst].set(content, mode="drop")


@_kv_pool
def write_page(
    pool: jax.Array,                 # (n_pages, page_size, Hkv, hd)
    new: jax.Array,                  # (B, Hkv, hd): one token per row
    block_table: jax.Array,          # (B, max_blocks) int32
    idx: jax.Array,                  # () or (B,) int32: absolute position
    active: Optional[jax.Array] = None,
) -> jax.Array:
    """Write one token's K or V through the block table.

    One fused scatter: each row lands at (page, slot) =
    (``bt[b, idx//P]``, ``idx % P``); rows that are inactive, out of range,
    or unmapped are routed to an out-of-bounds sentinel page and dropped.
    """
    n_pages, page_size = pool.shape[0], pool.shape[1]
    b, max_blocks = block_table.shape
    idx_b = jnp.broadcast_to(jnp.asarray(idx, jnp.int32).reshape(-1), (b,))
    blk = idx_b // page_size
    blk_c = jnp.clip(blk, 0, max_blocks - 1)
    page = jnp.take_along_axis(block_table, blk_c[:, None], axis=1)[:, 0]
    ok = (blk < max_blocks) & (page >= 0)
    if active is not None:
        ok &= active
    page = jnp.where(ok, page, n_pages)
    return pool.at[page, idx_b % page_size].set(
        new.astype(pool.dtype), mode="drop"
    )


@_kv_pool
def write_page_chunk(
    pool: jax.Array,                 # (n_pages, page_size, Hkv, hd)
    new: jax.Array,                  # (B, C, Hkv, hd): C tokens per row
    block_table: jax.Array,          # (B, max_blocks) int32
    start: jax.Array,                # () or (B,) int32: pos of chunk token 0
    width: jax.Array,                # () or (B,) int32: real tokens (1..C)
    active: Optional[jax.Array] = None,
) -> jax.Array:
    """Write a chunk of C tokens' K or V through the block table.

    One fused scatter: token ``i`` of row ``b`` lands at (page, slot) =
    (``bt[b, (start+i)//P]``, ``(start+i) % P``); chunk padding
    (``i >= width``), inactive rows, out-of-range and unmapped blocks are
    routed to the out-of-bounds sentinel page and dropped.  Positions are
    distinct within a row and pages are owned by a single row, so the
    scatter never writes one slot twice.
    """
    n_pages, page_size = pool.shape[0], pool.shape[1]
    b, max_blocks = block_table.shape
    c = new.shape[1]
    start_b = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (b,))
    w_b = jnp.broadcast_to(jnp.asarray(width, jnp.int32).reshape(-1), (b,))
    i = jnp.arange(c, dtype=jnp.int32)[None, :]
    posmat = start_b[:, None] + i                          # (B, C)
    blk = posmat // page_size
    blk_c = jnp.clip(blk, 0, max_blocks - 1)
    page = jnp.take_along_axis(block_table, blk_c, axis=1)  # (B, C)
    ok = (i < w_b[:, None]) & (blk < max_blocks) & (page >= 0)
    if active is not None:
        ok &= active[:, None]
    page = jnp.where(ok, page, n_pages)
    return pool.at[page, posmat % page_size].set(
        new.astype(pool.dtype), mode="drop"
    )


# ---------------------------------------------------------------------------
# Quantized writes (kv_dtype="int8"): int8 payload + per-(page, head)
# f32 scales.  Contract in the module docstring ("Quantized pools").
# ---------------------------------------------------------------------------

_QMAX = 127.0


def _quant_safe(scale: jax.Array) -> jax.Array:
    """Divide-safe scale: a zero scale encodes an all-zero payload, so any
    positive stand-in quantizes it to exact zeros."""
    return jnp.where(scale > 0, scale, 1.0)


@_kv_pool
def write_page_quant(
    pool: jax.Array,                 # (n_pages, page_size, Hkv, hd) int8
    scale: jax.Array,                # (n_pages, Hkv) f32
    new: jax.Array,                  # (B, Hkv, hd): one token per row
    block_table: jax.Array,          # (B, max_blocks) int32
    idx: jax.Array,                  # () or (B,) int32: absolute position
    active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``write_page`` for the quantized pool: returns ``(pool, scale)``.

    The target page's scale is reset at slot 0 and max-merged after; when
    it grows, the page's already-written slots are requantized in the
    same whole-page scatter that lands the new token.  Masking matches
    ``write_page`` exactly — a dropped payload write drops its scale
    update too, so the two pools can never disagree about a page.
    """
    n_pages, page_size = pool.shape[0], pool.shape[1]
    b, max_blocks = block_table.shape
    idx_b = jnp.broadcast_to(jnp.asarray(idx, jnp.int32).reshape(-1), (b,))
    blk = idx_b // page_size
    blk_c = jnp.clip(blk, 0, max_blocks - 1)
    page = jnp.take_along_axis(block_table, blk_c[:, None], axis=1)[:, 0]
    ok = (blk < max_blocks) & (page >= 0)
    if active is not None:
        ok &= active
    page_c = jnp.clip(page, 0, n_pages - 1)
    tgt = jnp.where(ok, page, n_pages)
    slot = idx_b % page_size

    newf = new.astype(jnp.float32)                       # (B, Hkv, hd)
    s_cand = jnp.max(jnp.abs(newf), axis=-1) / _QMAX     # (B, Hkv)
    s_old = scale[page_c]                                # (B, Hkv)
    fresh = (slot == 0)[:, None]
    s_new = jnp.where(fresh, s_cand, jnp.maximum(s_old, s_cand))
    # requantize the already-written slots when the scale grew; a fresh
    # page's stale payload rescales to zero (never read either way)
    ratio = jnp.where(fresh, 0.0, s_old / _quant_safe(s_new))
    content = pool[page_c].astype(jnp.float32)           # (B, S, Hkv, hd)
    merged = jnp.round(content * ratio[:, None, :, None])
    q_tok = jnp.round(newf / _quant_safe(s_new)[:, :, None])
    sl = jnp.arange(page_size, dtype=jnp.int32)[None, :, None, None]
    merged = jnp.where(sl == slot[:, None, None, None], q_tok[:, None],
                       merged)
    merged = jnp.clip(merged, -_QMAX, _QMAX)
    pool = pool.at[tgt].set(merged.astype(pool.dtype), mode="drop")
    scale = scale.at[tgt].set(s_new, mode="drop")
    return pool, scale


@_kv_pool
def write_page_chunk_quant(
    pool: jax.Array,                 # (n_pages, page_size, Hkv, hd) int8
    scale: jax.Array,                # (n_pages, Hkv) f32
    new: jax.Array,                  # (B, C, Hkv, hd): C tokens per row
    block_table: jax.Array,          # (B, max_blocks) int32
    start: jax.Array,                # () or (B,) int32: pos of chunk token 0
    width: jax.Array,                # () or (B,) int32: real tokens (1..C)
    active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``write_page_chunk`` for the quantized pool: ``(pool, scale)``.

    The f32 chunk write is one fused scatter, but the per-page scale must
    be updated once per *page* the chunk touches, so this unrolls the
    same ``(C-1)//page_size + 2``-rung ladder as ``alloc_range`` — rung
    ``k`` quantizes the sub-chunk landing in block ``start//P + k``
    against that page's merged scale (reset when the rung covers the
    page's slot 0, i.e. ``blk*P >= start``).  Rungs touch disjoint pages
    per row and masking matches ``write_page_chunk``.
    """
    n_pages, page_size = pool.shape[0], pool.shape[1]
    b, max_blocks = block_table.shape
    c = new.shape[1]
    start_b = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (b,))
    w_b = jnp.broadcast_to(jnp.asarray(width, jnp.int32).reshape(-1), (b,))
    if active is None:
        active = jnp.ones((b,), bool)
    i = jnp.arange(c, dtype=jnp.int32)[None, :]
    posmat = start_b[:, None] + i                        # (B, C)
    end_blk = (start_b + jnp.maximum(w_b, 1) - 1) // page_size
    start_blk = start_b // page_size
    newf = new.astype(jnp.float32)                       # (B, C, Hkv, hd)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    for k in range((c - 1) // page_size + 2):
        blk = start_blk + k
        on = active & (w_b > 0) & (blk <= end_blk) & (blk < max_blocks)
        blk_c = jnp.clip(blk, 0, max_blocks - 1)
        page = jnp.take_along_axis(block_table, blk_c[:, None], axis=1)[:, 0]
        on &= page >= 0
        page_c = jnp.clip(page, 0, n_pages - 1)
        tgt = jnp.where(on, page, n_pages)
        in_rung = (posmat // page_size == blk[:, None]) & (i < w_b[:, None])
        amax = jnp.max(
            jnp.where(in_rung[:, :, None, None], jnp.abs(newf), 0.0),
            axis=(1, 3),
        )                                                # (B, Hkv)
        s_cand = amax / _QMAX
        s_old = scale[page_c]
        fresh = (blk * page_size >= start_b)[:, None]
        s_new = jnp.where(fresh, s_cand, jnp.maximum(s_old, s_cand))
        ratio = jnp.where(fresh, 0.0, s_old / _quant_safe(s_new))
        content = pool[page_c].astype(jnp.float32)       # (B, S, Hkv, hd)
        merged = jnp.round(content * ratio[:, None, :, None])
        q_tok = jnp.round(newf / _quant_safe(s_new)[:, None, :, None])
        sl = jnp.where(in_rung, posmat % page_size, page_size)
        merged = merged.at[rows, sl].set(q_tok, mode="drop")
        merged = jnp.clip(merged, -_QMAX, _QMAX)
        pool = pool.at[tgt].set(merged.astype(pool.dtype), mode="drop")
        scale = scale.at[tgt].set(s_new, mode="drop")
    return pool, scale


@_kv_pool
def copy_page_scale(
    scales: jax.Array,  # (stacks, n_pages, Hkv) f32
    src: jax.Array,     # (B,) int32 page ids (n_pages sentinel = skip row)
    dst: jax.Array,     # (B,) int32 page ids (n_pages sentinel = skip row)
) -> jax.Array:
    """The CoW scale move: the fresh page inherits its donor's
    per-(page, head) scales so the prefix ``copy_page_prefix`` moved
    keeps dequantizing bit-identically.  Same ``n_pages`` sentinels as
    ``copy_page_prefix`` — rows that did not move drop."""
    n_pages = scales.shape[1]
    content = scales[:, jnp.clip(src, 0, n_pages - 1)]   # (stacks, B, Hkv)
    return scales.at[:, dst].set(content, mode="drop")
