"""Decoder-only LM family: dense / MoE / SSM (Mamba-2) / hybrid (Zamba2) /
VLM (Llama-3.2-Vision cross-attention).

Layer trunks are homogeneous and scanned (``lax.scan`` over stacked params)
so a 100-layer model compiles one layer body — essential for the 512-device
dry-run.  Heterogeneous patterns (Zamba2's shared attention every N blocks,
Vision's cross-attention every N layers) scan over *groups*.

Public API (all pure functions of (cfg, params, ...)):
    init_params, train_loss, forward, lm_logits,
    init_decode_state, decode_step, prefill, prefill_chunk
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models import components as C
from repro.kernels import ops


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _stacked(init_fn, rng, n: int):
    return jax.vmap(init_fn)(jax.random.split(rng, n))


def _init_layer_dense(cfg):
    def f(rng):
        r1, r2 = jax.random.split(rng)
        return {"attn": C.init_attention(cfg, r1), "mlp": C.init_mlp(cfg, r2)}
    return f


def _init_layer_moe(cfg):
    def f(rng):
        r1, r2 = jax.random.split(rng)
        return {"attn": C.init_attention(cfg, r1), "moe": C.init_moe(cfg, r2)}
    return f


def _init_layer_mamba(cfg):
    def f(rng):
        return {"mamba": C.init_mamba(cfg, rng)}
    return f


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ArchConfig, rng) -> Dict[str, Any]:
    """Random params from ``rng``.  Jitted so each full-size f32 ``normal``
    draw fuses into its scale-and-cast: at published widths only the
    param-dtype outputs reach device memory, never an f32 temporary the
    size of a stacked weight."""
    dt = cfg.dtype_()
    r_emb, r_layers, r_head, r_extra = jax.random.split(rng, 4)
    params: Dict[str, Any] = {
        "embed": (
            jax.random.normal(r_emb, (cfg.vocab_size, cfg.d_model)) * 0.02
        ).astype(dt),
        "ln_f": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(r_head, (cfg.d_model, cfg.vocab_size))
            / np.sqrt(cfg.d_model)
        ).astype(dt)
    fam = cfg.family
    if fam in ("dense",):
        params["layers"] = _stacked(_init_layer_dense(cfg), r_layers, cfg.n_layers)
    elif fam == "moe":
        params["layers"] = _stacked(_init_layer_moe(cfg), r_layers, cfg.n_layers)
    elif fam == "ssm":
        params["layers"] = _stacked(_init_layer_mamba(cfg), r_layers, cfg.n_layers)
    elif fam == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        def group(rng):
            return _stacked(_init_layer_mamba(cfg), rng, cfg.attn_every)
        params["groups"] = _stacked(group, r_layers, g)
        ra, rm = jax.random.split(r_extra)
        params["shared_attn"] = C.init_attention(cfg, ra)
        params["shared_mlp"] = C.init_mlp(cfg, rm)
    elif fam == "vlm":
        g = cfg.n_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every - 1
        def group(rng):
            return _stacked(_init_layer_dense(cfg), rng, per)
        params["groups"] = _stacked(group, r_layers, g)
        params["cross"] = _stacked(
            lambda r: {
                "attn": C.init_attention(cfg, r, cross=True),
                "mlp": C.init_mlp(cfg, jax.random.fold_in(r, 1)),
            },
            r_extra, g,
        )
    else:
        raise ValueError(f"init_params: unsupported family {fam}")
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _residual_dtype(cfg: ArchConfig):
    """The residual stream's dtype: float32 where the model keeps it so
    (``residual_f32``, the ssm family), else the served dtype.  Blocks
    normalize it and project in the served dtype."""
    return jnp.float32 if cfg.residual_f32 else cfg.dtype_()


def _layer_apply(cfg: ArchConfig, p, x, positions):
    if "mamba" in p:
        return C.mamba_block(cfg, p["mamba"], x)
    x = C.attention_block(
        cfg, p["attn"], x, positions=positions, causal=True, window=cfg.window
    )
    if "moe" in p:
        return C.moe_block(cfg, p["moe"], x)
    return C.mlp_block(cfg, p["mlp"], x)


def forward(
    cfg: ArchConfig,
    params,
    tokens: jax.Array,                  # (B, S)
    *,
    vision: Optional[jax.Array] = None,  # (B, P, d) VLM patch embeddings
    remat: bool = True,
) -> jax.Array:
    x = params["embed"][tokens].astype(_residual_dtype(cfg))
    x = shard(x, ("data", "sp", None))
    s = tokens.shape[1]
    positions = jnp.arange(s)

    def layer(x, p):
        # residual stream is sequence-parallel between blocks (SP)
        return shard(_layer_apply(cfg, p, x, positions), ("data", "sp", None)), None

    if remat:
        layer = jax.checkpoint(layer)

    if cfg.family in ("dense", "moe", "ssm"):
        x, _ = jax.lax.scan(layer, x, params["layers"])
    elif cfg.family == "hybrid":
        def group(x, gp):
            x, _ = jax.lax.scan(layer, x, gp["inner"])
            x = C.attention_block(
                cfg, gp["shared_attn"], x, positions=positions, causal=True
            )
            x = C.mlp_block(cfg, gp["shared_mlp"], x)
            return x, None
        if remat:
            group = jax.checkpoint(group)
        # shared params broadcast into every group step
        g = cfg.n_layers // cfg.attn_every
        gp = {
            "inner": params["groups"],
            "shared_attn": jax.tree.map(
                lambda l: jnp.broadcast_to(l, (g, *l.shape)), params["shared_attn"]
            ),
            "shared_mlp": jax.tree.map(
                lambda l: jnp.broadcast_to(l, (g, *l.shape)), params["shared_mlp"]
            ),
        }
        x, _ = jax.lax.scan(group, x, gp)
    elif cfg.family == "vlm":
        assert vision is not None, "vlm needs vision embeddings"
        def group(x, gp):
            x, _ = jax.lax.scan(layer, x, gp["self"])
            x = C.attention_block(
                cfg, gp["cross"]["attn"], x, kv_src=vision, causal=False
            )
            x = C.mlp_block(cfg, gp["cross"]["mlp"], x)
            return x, None
        if remat:
            group = jax.checkpoint(group)
        x, _ = jax.lax.scan(
            group, x, {"self": params["groups"], "cross": params["cross"]}
        )
    else:
        raise ValueError(cfg.family)
    return C.norm(cfg, params["ln_f"], x)


def lm_logits(cfg: ArchConfig, params, h: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return C.dense(h.astype(cfg.dtype_()), w)


def _xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Stable mean NLL in f32 over (..., V) logits."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    picked = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return (lse - picked).mean()


def train_loss(cfg: ArchConfig, params, batch: Dict[str, jax.Array]):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    inputs = shard(inputs, ("data", None))
    h = forward(cfg, params, inputs, vision=batch.get("vision"))
    logits = lm_logits(cfg, params, h)
    logits = shard(logits, ("data", None, "model"))
    return _xent(logits, targets)


# ---------------------------------------------------------------------------
# Serving: prefill + decode with KV / SSM caches
# ---------------------------------------------------------------------------

def init_decode_state(
    cfg: ArchConfig, batch: int, max_len: int, *, per_row_pos: bool = False,
    layout: str = "contiguous", page_size: int = 16,
    n_pages: Optional[int] = None, snapshots: bool = False,
    host_spill: bool = False, kv_dtype: str = "f32", cache=None,
) -> Dict[str, jax.Array]:
    """Decode caches.  ``per_row_pos=True`` keeps ``pos`` as a (B,) vector so
    rows may sit at different sequence depths (continuous batching).

    ``cache=`` accepts a ``repro.serving.config.CacheConfig`` (duck-typed
    — models never import serving) and overrides the individual layout
    kwargs, which remain for legacy call sites.

    ``layout`` picks the KV-cache representation (``KVCacheLayout``):
    ``"contiguous"`` is the dense ``(layers, B, max_len, Hkv, hd)`` slab;
    ``"paged"`` replaces it with a page pool + per-row block table + free
    list (see ``repro.serving.pager`` for the layout contract), so resident
    KV memory scales with live tokens instead of ``B x max_len``.  SSM and
    conv state is recurrent (O(1) per row) and stays contiguous under
    either layout; only attention K/V pages.

    ``snapshots=True`` (recurrent families, paged layout only) adds the
    page-boundary recurrent-state snapshot store: slot pools for the full
    per-row SSM + conv state, a per-(row, boundary) slot table and a
    refcounted free list, managed by the same allocator primitives as KV
    pages (``repro.serving.pager`` documents the snapshot-slot contract).
    Snapshots are what make prompt prefix sharing real for ssm/hybrid: a
    sharer restores the donor's state at the last shared page boundary
    instead of re-running the recurrence.  Attention-only families ignore
    the flag (they have no recurrent carry to snapshot).

    ``host_spill=True`` (paged layout with KV pages only) adds the host
    tier behind preemption: mirror pools (``hkp``/``hvp`` and, with
    snapshots, ``hsnap_ssm``/``hsnap_conv``), per-row host tables, and a
    second refcounted free list per space, all sized at the worst case
    (``batch x max_blocks`` slots) so a spill can never find the host
    free list dry.  ``spill_rows``/``restore_rows`` move a row between
    tiers; families without KV pages (pure ssm, contiguous layouts)
    ignore the flag — they have no page pool to relieve, so the engine
    never preempts them.

    ``kv_dtype="int8"`` (paged layout only) stores the KV page pools as
    symmetric per-(page, head)-scaled int8: the payload arrays switch to
    ``jnp.int8`` and f32 scale pools ``ksc``/``vsc`` (shape
    ``(stacks, n_pages, Hkv)``) ride alongside — written by
    ``pager.write_page_quant``/``write_page_chunk_quant``, dequantized
    inside the attention kernels.  Host-tier mirrors (``hksc``/``hvsc``)
    spill the quantized form, cutting spill bandwidth the same 4x.
    ``kv_dtype="bf16"`` is the storage-only midpoint: half-width pools
    through the unmodified kernels (which upcast K/V tiles to f32), no
    scale pools, exactly half the f32 resident bytes.
    """
    if cache is not None:
        layout = cache.layout
        page_size = cache.page_size
        n_pages = cache.n_pages
        snapshots = cache.snapshots
        host_spill = bool(cache.host_spill)
        kv_dtype = getattr(cache, "kv_dtype", "f32")
    if layout not in ("contiguous", "paged"):
        raise ValueError(f"unknown KV-cache layout {layout!r}")
    if kv_dtype not in ("f32", "bf16", "int8"):
        raise ValueError(
            f"unknown kv_dtype {kv_dtype!r} "
            "(expected 'f32', 'bf16', or 'int8')"
        )
    if kv_dtype != "f32" and layout != "paged":
        raise ValueError(
            "sub-f32 KV storage is a paged-pool feature (quantized "
            "scales are per page) — layout='paged' required for "
            f"kv_dtype={kv_dtype!r}"
        )
    dt = cfg.dtype_()
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    # sliding-window archs only ever need `window` cache slots (ring buffer)
    eff = min(max_len, cfg.window) if cfg.window else max_len
    pos0 = jnp.zeros((batch,) if per_row_pos else (), jnp.int32)
    state: Dict[str, jax.Array] = {"pos": pos0}
    recurrent = cfg.family in ("ssm", "hybrid")
    if snapshots and recurrent and layout != "paged":
        raise ValueError(
            "recurrent-state snapshots use page-boundary granularity — "
            "layout='paged' required"
        )

    def paged_kv(stacks: int) -> Dict[str, jax.Array]:
        # paged writes at *absolute* positions (no window ring): block ids
        # are position // page_size, so the table covers max_len
        from repro.serving import pager as P

        max_blocks = -(-max_len // page_size)
        pages = batch * max_blocks if n_pages is None else n_pages
        ps = P.init_pager(pages)
        quant = kv_dtype == "int8"
        kv_dt = {"int8": jnp.int8, "bf16": jnp.bfloat16}.get(kv_dtype, dt)
        out = {
            "kp": jnp.zeros((stacks, pages, page_size, hkv, hd), kv_dt),
            "vp": jnp.zeros((stacks, pages, page_size, hkv, hd), kv_dt),
            "block_table": P.init_block_table(batch, max_blocks),
            "page_free": ps.free,
            "page_top": ps.top,
            "page_rc": ps.rc,
        }
        if quant:
            # per-(page, head) f32 scales — zero means "empty page"
            # (write_page_quant resets the scale at slot 0)
            out["ksc"] = jnp.zeros((stacks, pages, hkv), jnp.float32)
            out["vsc"] = jnp.zeros((stacks, pages, hkv), jnp.float32)
        if host_spill:
            # host tier: worst-case sizing (every row fully resident, all
            # spilled at once) so spill pops can never run dry
            n_hslots = batch * max_blocks
            hs = P.init_pager(n_hslots)
            out.update({
                "hkp": jnp.zeros(
                    (stacks, n_hslots, page_size, hkv, hd), kv_dt
                ),
                "hvp": jnp.zeros(
                    (stacks, n_hslots, page_size, hkv, hd), kv_dt
                ),
                "host_table": P.init_block_table(batch, max_blocks),
                "host_free": hs.free,
                "host_top": hs.top,
                "host_rc": hs.rc,
            })
            if quant:
                # spill moves the quantized payload + its scales; the
                # host tier never re-quantizes
                out["hksc"] = jnp.zeros(
                    (stacks, n_hslots, hkv), jnp.float32
                )
                out["hvsc"] = jnp.zeros(
                    (stacks, n_hslots, hkv), jnp.float32
                )
        return out

    def snap_store(host: bool = False) -> Dict[str, jax.Array]:
        # worst-case slot pool: every row can snapshot every boundary it
        # can ever reach, so — like the page reservation ledger — the
        # allocator can never run dry mid-request (slots a dead donor
        # leaves behind are mapped, hence budgeted, by their sharers)
        from repro.serving import pager as P

        n_bound = -(-max_len // page_size)
        n_slots = batch * n_bound
        ps = P.init_pager(n_slots)
        out = {
            "snap_ssm": jnp.zeros(
                (n_slots, cfg.n_layers, cfg.ssm_heads, cfg.ssm_head_dim,
                 cfg.ssm_state), jnp.float32,
            ),
            "snap_conv": jnp.zeros(
                (n_slots, cfg.n_layers, cfg.ssm_conv - 1, cfg.conv_dim), dt
            ),
            "snap_table": P.init_block_table(batch, n_bound),
            "snap_free": ps.free,
            "snap_top": ps.top,
            "snap_rc": ps.rc,
        }
        if host:
            # host snapshot tier (spillable families only): boundary space
            # mirrors at the same worst case as the device slot pool
            hs = P.init_pager(n_slots)
            out.update({
                "hsnap_ssm": jnp.zeros_like(out["snap_ssm"]),
                "hsnap_conv": jnp.zeros_like(out["snap_conv"]),
                "hsnap_table": P.init_block_table(batch, n_bound),
                "hsnap_free": hs.free,
                "hsnap_top": hs.top,
                "hsnap_rc": hs.rc,
            })
        return out

    if cfg.family in ("dense", "moe"):
        if layout == "paged":
            state.update(paged_kv(cfg.n_layers))
            return state
        state["k"] = jnp.zeros((cfg.n_layers, batch, eff, hkv, hd), dt)
        state["v"] = jnp.zeros((cfg.n_layers, batch, eff, hkv, hd), dt)
    elif cfg.family == "ssm":
        state["ssm"] = jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32,
        )
        state["conv"] = jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_conv - 1, cfg.conv_dim), dt
        )
        if snapshots:
            state.update(snap_store())
    elif cfg.family == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        state["ssm"] = jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32,
        )
        state["conv"] = jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_conv - 1, cfg.conv_dim), dt
        )
        if snapshots:
            state.update(snap_store(host=host_spill and layout == "paged"))
        if layout == "paged":
            state.update(paged_kv(g))
            return state
        state["k"] = jnp.zeros((g, batch, eff, hkv, hd), dt)
        state["v"] = jnp.zeros((g, batch, eff, hkv, hd), dt)
    elif cfg.family == "vlm":
        if layout == "paged":
            raise NotImplementedError(
                "paged KV layout: vlm's grouped self-attn cache not yet "
                "paged (serving engine families are dense/moe/ssm/hybrid)"
            )
        g = cfg.n_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every - 1
        state["k"] = jnp.zeros((g, per, batch, eff, hkv, hd), dt)
        state["v"] = jnp.zeros((g, per, batch, eff, hkv, hd), dt)
        # cross K/V filled by prefill from vision embeddings
        state["xk"] = jnp.zeros((g, batch, cfg.n_vision_tokens, hkv, hd), dt)
        state["xv"] = jnp.zeros((g, batch, cfg.n_vision_tokens, hkv, hd), dt)
    else:
        raise ValueError(cfg.family)
    return state


def _cache_index(cfg: ArchConfig, pos: jax.Array) -> jax.Array:
    return pos % cfg.window if cfg.window else pos


def _cache_update(cfg: ArchConfig, cache: jax.Array, new: jax.Array,
                  idx: jax.Array) -> jax.Array:
    """Write one token's K/V at ``idx`` into a (B, S, Hkv, hd) cache.

    When the cache's sequence dim is sharded (context-parallel decode for
    GQA head counts below the TP degree), a dynamic-update-slice forces
    GSPMD to all-gather the cache; an elementwise masked write partitions
    cleanly instead (perf iteration D2, §Perf).
    """
    from repro.distributed.sharding import active_mesh, axis_size

    tp = max(axis_size("model"), 1)
    seq_sharded = (
        active_mesh() is not None
        and cfg.n_kv_heads % tp != 0
        and cache.shape[1] % tp == 0
    )
    if seq_sharded or idx.ndim == 1:
        # per-row idx (continuous batching) uses the same elementwise masked
        # write — each row lands at its own slot in one fused op
        pos_iota = jax.lax.broadcasted_iota(
            jnp.int32, (1, cache.shape[1], 1, 1), 1
        )
        idx_b = idx.reshape(-1, 1, 1, 1) if idx.ndim == 1 else idx
        return jnp.where(pos_iota == idx_b, new[:, None].astype(cache.dtype),
                         cache)
    return jax.lax.dynamic_update_slice_in_dim(
        cache, new[:, None], idx, axis=1
    )


def _cache_update_chunk(cache: jax.Array, new: jax.Array,
                        posmat: jax.Array, valid: jax.Array) -> jax.Array:
    """Write a chunk of C tokens' K/V into a (B, S, Hkv, hd) cache.

    ``new`` is (B, C, Hkv, hd); token i of row b lands at absolute position
    ``posmat[b, i]``; invalid positions (chunk padding, inactive rows) are
    routed past the sequence axis and dropped.  Positions are distinct per
    row, so the scatter never writes one slot twice.  Absolute positions
    only — ring-indexed sliding-window caches can't host multi-token chunks
    (the chunk's own writes would recycle slots its queries still read).
    """
    smax = cache.shape[1]
    tgt = jnp.where(valid, posmat, smax)
    rows = jnp.arange(cache.shape[0])[:, None]
    return cache.at[rows, tgt].set(new.astype(cache.dtype), mode="drop")


def _paged_cow(state, wpos, active, *, cow: bool):
    """Shared head of every paged write path: unpack the allocator, and —
    when the engine can share pages (``cow``, a trace-time constant) — run
    copy-on-write for the page each row writes at ``wpos``, moving the
    already-written slot prefix into the private copy in both pools.
    Returns ``(state, PagerState, block_table)``; the caller allocs into
    ``bt`` and commits with ``_paged_commit``."""
    from repro.serving import pager as PG

    pstate = PG.PagerState(
        state["page_free"], state["page_top"], state["page_rc"]
    )
    bt = state["block_table"]
    if cow:
        pstate, bt, src, dst, lim, _ = PG.cow_on_write(
            pstate, bt, wpos, active, page_size=state["kp"].shape[2]
        )
        state = {**state,
                 "kp": PG.copy_page_prefix(state["kp"], src, dst, lim),
                 "vp": PG.copy_page_prefix(state["vp"], src, dst, lim)}
        if "ksc" in state:
            # quantized pools: the private copy inherits the donor page's
            # scale, so the copied prefix stays decodable; the next write
            # max-merges (and requantizes) from there
            state = {**state,
                     "ksc": PG.copy_page_scale(state["ksc"], src, dst),
                     "vsc": PG.copy_page_scale(state["vsc"], src, dst)}
    return state, pstate, bt


def _paged_commit(state, pstate, bt):
    return {**state, "page_free": pstate.free, "page_top": pstate.top,
            "page_rc": pstate.rc, "block_table": bt}


def _snap_capture(state, pos_after: jax.Array, active: jax.Array,
                  snap_every: int):
    """Write a page-boundary recurrent-state snapshot for every row whose
    step just ended exactly at a boundary (``pos_after`` a positive
    multiple of ``snap_every``): allocate a slot for boundary index
    ``pos_after/snap_every - 1`` in the row's snapshot table (boundary
    space is block space with page_size 1 — same allocator, same
    conservation invariant) and scatter the row's full-depth SSM + conv
    state into the pools.  Pure ``jnp``, fixed shapes, one masked scatter
    per pool — runs inside the jitted engine steps without retracing.

    A slot still shared with a peer (rc > 1) is never overwritten: shared
    slots sit strictly below the row's own progress (a sharer resumes past
    its inherited boundaries), so the guard is belt-and-braces for the
    immutability of shared snapshots — the same read-only contract as
    shared KV pages.
    """
    from repro.serving import pager as PG

    at = active & (pos_after > 0) & (pos_after % snap_every == 0)
    bound = pos_after // snap_every - 1
    sstate = PG.PagerState(
        state["snap_free"], state["snap_top"], state["snap_rc"]
    )
    sstate, stbl = PG.alloc_on_write(
        sstate, state["snap_table"], bound, at, page_size=1
    )
    n_slots = state["snap_ssm"].shape[0]
    nb = stbl.shape[1]
    slot = jnp.take_along_axis(
        stbl, jnp.clip(bound, 0, nb - 1)[:, None], axis=1
    )[:, 0]
    ok = at & (bound >= 0) & (bound < nb) & (slot >= 0)
    ok &= sstate.rc[jnp.clip(slot, 0, n_slots - 1)] <= 1
    tgt = jnp.where(ok, slot, n_slots)                 # sentinel: dropped
    snap_ssm = state["snap_ssm"].at[tgt].set(
        jnp.moveaxis(state["ssm"], 1, 0), mode="drop"
    )
    snap_conv = state["snap_conv"].at[tgt].set(
        jnp.moveaxis(state["conv"], 1, 0).astype(state["snap_conv"].dtype),
        mode="drop",
    )
    return {**state, "snap_ssm": snap_ssm, "snap_conv": snap_conv,
            "snap_table": stbl, "snap_free": sstate.free,
            "snap_top": sstate.top, "snap_rc": sstate.rc}


def restore_snapshots(state, mask: jax.Array, src: jax.Array,
                      nblk: jax.Array):
    """Prefix-sharing admission for recurrent state: map the donor rows'
    leading ``nblk`` snapshot slots into the masked rows' tables
    (``pager.share_prefix`` on boundary space — refcount bumps keep the
    slots alive past the donor's release) and load slot ``nblk - 1`` —
    the donor's state after its first ``nblk`` pages — into the rows'
    live SSM/conv state, so prefill resumes at the first unshared token
    with the recurrence already advanced.  ``nblk == 0`` rows are
    untouched (the non-sharing admission path is the same trace).
    """
    from repro.serving import pager as PG

    sstate, stbl = PG.share_prefix(
        PG.PagerState(state["snap_free"], state["snap_top"],
                      state["snap_rc"]),
        state["snap_table"], src, nblk, mask,
    )
    b = stbl.shape[0]
    nb = stbl.shape[1]
    nblk_b = jnp.broadcast_to(jnp.asarray(nblk, jnp.int32).reshape(-1), (b,))
    k = jnp.clip(nblk_b - 1, 0, nb - 1)
    slot = jnp.take_along_axis(stbl, k[:, None], axis=1)[:, 0]
    ok = mask & (nblk_b > 0) & (slot >= 0)
    n_slots = state["snap_ssm"].shape[0]
    sl = jnp.clip(slot, 0, n_slots - 1)
    ssm_r = jnp.moveaxis(state["snap_ssm"][sl], 0, 1)      # (L, B, ...)
    conv_r = jnp.moveaxis(state["snap_conv"][sl], 0, 1)
    return {**state,
            "ssm": jnp.where(ok[None, :, None, None, None], ssm_r,
                             state["ssm"]),
            "conv": jnp.where(ok[None, :, None, None],
                              conv_r.astype(state["conv"].dtype),
                              state["conv"]),
            "snap_table": stbl, "snap_free": sstate.free,
            "snap_top": sstate.top, "snap_rc": sstate.rc}


def spill_rows(
    cfg: ArchConfig, state: Dict[str, jax.Array], mask: jax.Array,  # (B,) bool
) -> Dict[str, jax.Array]:
    """Preemption: move the masked rows' KV pages — and, with a snapshot
    store, their boundary snapshot slots — to the host tier.

    Bookkeeping runs through ``pager.spill_rows`` (host slot per mapped
    block, then a device-side release; shared pages stay resident for
    their peers while the victim gets a private host copy) and the data
    moves through ``pager.copy_pages`` in the same jitted call.  The
    row's *lane* state (``pos``, live ssm/conv carries) stays in place —
    a spilled row keeps its slot and simply idles with ``active=False``;
    only pool residency moves.  Requires
    ``init_decode_state(host_spill=True)``.
    """
    from repro.serving import pager as PG

    if "host_table" not in state:
        raise ValueError(
            "spill_rows needs init_decode_state(host_spill=True) paged state"
        )
    pstate = PG.PagerState(
        state["page_free"], state["page_top"], state["page_rc"]
    )
    hstate = PG.PagerState(
        state["host_free"], state["host_top"], state["host_rc"]
    )
    pstate, bt, hstate, ht, src, dst = PG.spill_rows(
        pstate, state["block_table"], hstate, state["host_table"], mask
    )
    out = {**state,
           "hkp": PG.copy_pages(state["hkp"], state["kp"], src, dst),
           "hvp": PG.copy_pages(state["hvp"], state["vp"], src, dst),
           "block_table": bt, "page_free": pstate.free,
           "page_top": pstate.top, "page_rc": pstate.rc,
           "host_table": ht, "host_free": hstate.free,
           "host_top": hstate.top, "host_rc": hstate.rc}
    if "hksc" in state:
        # quantized pools spill as-is: int8 payload + f32 scales move with
        # the same (src, dst) slot vectors, so host copies stay decodable
        out["hksc"] = PG.copy_pages(state["hksc"], state["ksc"], src, dst)
        out["hvsc"] = PG.copy_pages(state["hvsc"], state["vsc"], src, dst)
    if "hsnap_table" in state:
        sstate = PG.PagerState(
            state["snap_free"], state["snap_top"], state["snap_rc"]
        )
        hs = PG.PagerState(
            state["hsnap_free"], state["hsnap_top"], state["hsnap_rc"]
        )
        sstate, stbl, hs, hstbl, ssrc, sdst = PG.spill_rows(
            sstate, state["snap_table"], hs, state["hsnap_table"], mask
        )
        out.update({
            "hsnap_ssm": PG.copy_pages(
                state["hsnap_ssm"], state["snap_ssm"], ssrc, sdst, axis=0
            ),
            "hsnap_conv": PG.copy_pages(
                state["hsnap_conv"], state["snap_conv"], ssrc, sdst, axis=0
            ),
            "snap_table": stbl, "snap_free": sstate.free,
            "snap_top": sstate.top, "snap_rc": sstate.rc,
            "hsnap_table": hstbl, "hsnap_free": hs.free,
            "hsnap_top": hs.top, "hsnap_rc": hs.rc,
        })
    return out


def restore_rows(
    cfg: ArchConfig, state: Dict[str, jax.Array], mask: jax.Array,  # (B,) bool
) -> Dict[str, jax.Array]:
    """The exact mirror of ``spill_rows``: re-allocate device pages (and
    snapshot slots) for the masked rows' host-table entries, copy the
    content back, and release the host slots.  A restored row owns its
    pages privately (rc == 1) even where it used to share — the caller's
    reservation ledger must already cover the row's worst-case page
    count so the device pops cannot run dry."""
    from repro.serving import pager as PG

    if "host_table" not in state:
        raise ValueError(
            "restore_rows needs init_decode_state(host_spill=True) paged state"
        )
    pstate = PG.PagerState(
        state["page_free"], state["page_top"], state["page_rc"]
    )
    hstate = PG.PagerState(
        state["host_free"], state["host_top"], state["host_rc"]
    )
    pstate, bt, hstate, ht, src, dst = PG.restore_rows(
        pstate, state["block_table"], hstate, state["host_table"], mask
    )
    out = {**state,
           "kp": PG.copy_pages(state["kp"], state["hkp"], src, dst),
           "vp": PG.copy_pages(state["vp"], state["hvp"], src, dst),
           "block_table": bt, "page_free": pstate.free,
           "page_top": pstate.top, "page_rc": pstate.rc,
           "host_table": ht, "host_free": hstate.free,
           "host_top": hstate.top, "host_rc": hstate.rc}
    if "hksc" in state:
        out["ksc"] = PG.copy_pages(state["ksc"], state["hksc"], src, dst)
        out["vsc"] = PG.copy_pages(state["vsc"], state["hvsc"], src, dst)
    if "hsnap_table" in state:
        sstate = PG.PagerState(
            state["snap_free"], state["snap_top"], state["snap_rc"]
        )
        hs = PG.PagerState(
            state["hsnap_free"], state["hsnap_top"], state["hsnap_rc"]
        )
        sstate, stbl, hs, hstbl, ssrc, sdst = PG.restore_rows(
            sstate, state["snap_table"], hs, state["hsnap_table"], mask
        )
        out.update({
            "snap_ssm": PG.copy_pages(
                state["snap_ssm"], state["hsnap_ssm"], ssrc, sdst, axis=0
            ),
            "snap_conv": PG.copy_pages(
                state["snap_conv"], state["hsnap_conv"], ssrc, sdst, axis=0
            ),
            "snap_table": stbl, "snap_free": sstate.free,
            "snap_top": sstate.top, "snap_rc": sstate.rc,
            "hsnap_table": hstbl, "hsnap_free": hs.free,
            "hsnap_top": hs.top, "hsnap_rc": hs.rc,
        })
    return out


def decode_step(
    cfg: ArchConfig, params, state, token: jax.Array,  # (B,) int32
    *, active: Optional[jax.Array] = None,             # (B,) bool
    cow: bool = False, snap_every: int = 0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One token for every sequence in the batch; returns (logits, state).

    ``state["pos"]`` may be a scalar (all rows in lockstep) or a (B,) vector
    (rows at independent depths — the continuous-batching serving engine).

    ``active`` (requires per-row ``pos``) masks rows that are between
    requests: their caches are not written, no pages are allocated, and
    their ``pos`` does not advance.  The layout is picked by the state dict
    itself: a ``block_table`` key means paged (see ``repro.serving.pager``
    for the contract), otherwise the contiguous slab path runs unchanged.

    ``cow`` (trace-time constant) enables the copy-on-write pass before
    paged writes — required exactly when pages can be prefix-shared
    (``pager.share_prefix`` ran on this state); engines that never share
    skip the per-step page gather/scatter entirely.

    ``snap_every`` (trace-time constant; recurrent families with a
    snapshot store) captures the row's post-step SSM/conv state whenever
    the step lands exactly on a page boundary — a decode step ends at
    every successive position, so every boundary it reaches is captured.
    """
    pos = state["pos"]
    paged = "block_table" in state
    quant = paged and "ksc" in state    # trace-time: int8 KV pools
    # the named scopes below (embed, layers, attention, mlp, ssm, kv_pool,
    # lm_head) label every op of the step in the compiled program's op
    # metadata, so a device trace can attribute time to each part
    with jax.named_scope("embed"):
        x = params["embed"][token].astype(_residual_dtype(cfg))  # (B, d)
    # paged layout uses absolute positions (window masking in attention);
    # the contiguous layout ring-indexes sliding-window caches
    idx = pos if paged else _cache_index(cfg, pos)
    if cfg.window and not paged:
        cache_len = jnp.minimum(pos + 1, cfg.window)
    else:
        cache_len = pos + 1
    rope_pos = pos[..., None] if pos.ndim == 1 else pos[None]

    if paged:
        from repro.serving import pager as PG

        # copy-on-write before the write: a row whose target page is
        # prefix-shared (rc > 1) moves to a private copy first, so the
        # write can never corrupt a peer's cache
        state, pstate, bt = _paged_cow(state, idx, active, cow=cow)
        pstate, bt = PG.alloc_on_write(
            pstate, bt, idx, active, page_size=state["kp"].shape[2]
        )
        state = _paged_commit(state, pstate, bt)
    # contiguous masked-write: routing inactive rows to slot -1 drops them
    if active is not None and not paged and idx.ndim == 1:
        w_idx = jnp.where(active, idx, -1)
    else:
        w_idx = idx

    def attn_dec(p, x, kv):
        # ``kv`` is the per-layer cache tuple: (ck, cv) — or, quantized,
        # (ck, cv, ksc, vsc) with the scale pools riding the same scan
        b, d = x.shape
        hkv, hd = cfg.n_kv_heads, cfg.head_dim_
        xn = C.norm(cfg, p["ln"], x)
        q = C.dense(xn, p["wq"], p.get("bq")).reshape(b, cfg.n_heads, hd)
        k_new = C.dense(xn, p["wk"], p.get("bk")).reshape(b, hkv, hd)
        v_new = C.dense(xn, p["wv"], p.get("bv")).reshape(b, hkv, hd)
        cos, sin = C.rope_freqs(cfg, rope_pos)
        q = C.apply_rope(q.reshape(b, 1, -1, hd), cos, sin).reshape(b, -1, hd)
        k_new = C.apply_rope(
            k_new.reshape(b, 1, hkv, hd), cos, sin
        ).reshape(b, hkv, hd)
        if paged:
            from repro.serving import pager as PG

            bt = state["block_table"]
            if quant:
                ck, cv, ksc, vsc = kv
                ck, ksc = PG.write_page_quant(ck, ksc, k_new, bt, idx,
                                              active)
                cv, vsc = PG.write_page_quant(cv, vsc, v_new, bt, idx,
                                              active)
                o = ops.attention_decode(
                    q, ck, cv, jnp.asarray(cache_len, jnp.int32),
                    block_table=bt, window=cfg.window,
                    kv_scales=(ksc, vsc),
                )
                kv = (ck, cv, ksc, vsc)
            else:
                ck, cv = kv
                ck = PG.write_page(ck, k_new, bt, idx, active)
                cv = PG.write_page(cv, v_new, bt, idx, active)
                o = ops.attention_decode(
                    q, ck, cv, jnp.asarray(cache_len, jnp.int32),
                    block_table=bt, window=cfg.window,
                )
                kv = (ck, cv)
        else:
            ck, cv = kv
            ck = _cache_update(cfg, ck, k_new, w_idx)
            cv = _cache_update(cfg, cv, v_new, w_idx)
            o = ops.attention_decode(
                q, ck, cv, jnp.asarray(cache_len, jnp.int32)
            )
            kv = (ck, cv)
        return x + C.dense(o.reshape(b, -1), p["wo"]), kv

    def mlp_dec(p, x):
        xn = C.norm(cfg, p["ln"], x)
        h = jax.nn.silu(C.dense(xn, p["wg"])) * C.dense(xn, p["wi"])
        return x + C.dense(h, p["wo"])

    def moe_dec(p, x):
        return C.moe_block(cfg, p, x[:, None, :])[:, 0, :]

    kk, vk = ("kp", "vp") if paged else ("k", "v")
    # scan xs carry the per-layer cache stacks; quantized pools append
    # their scale stacks so the whole cache moves through one scan
    kv_keys = (kk, vk) + (("ksc", "vsc") if quant else ())

    fam = cfg.family
    if fam in ("dense", "moe"):
        def body(x, inp):
            p, kv = inp[0], inp[1:]
            with jax.named_scope("attention"):
                x, kv = attn_dec(p["attn"], x, kv)
            with jax.named_scope("mlp"):
                x = (moe_dec(p["moe"], x) if "moe" in p
                     else mlp_dec(p["mlp"], x))
            return x, kv
        with jax.named_scope("layers"):
            x, kv_out = jax.lax.scan(
                body, x,
                (params["layers"],) + tuple(state[k] for k in kv_keys),
            )
        state = {**state, **dict(zip(kv_keys, kv_out))}
    elif fam == "ssm":
        # inactive (idle or spilled) rows must carry their recurrent state
        # through *untouched* — a spilled row's live ssm/conv is the part
        # of its context that never leaves the lane
        val = active[:, None] if active is not None else None

        def body(x, inp):
            p, s_ssm, s_conv = inp
            with jax.named_scope("ssm"):
                x, s_ssm, s_conv = C.mamba_decode_block(
                    cfg, p["mamba"], x, s_ssm, s_conv, valid=val
                )
            return x, (s_ssm, s_conv)
        with jax.named_scope("layers"):
            x, (ssm, conv) = jax.lax.scan(
                body, x, (params["layers"], state["ssm"], state["conv"])
            )
        state = {**state, "ssm": ssm, "conv": conv}
    elif fam == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        a = cfg.attn_every
        ssm_g = state["ssm"].reshape(g, a, *state["ssm"].shape[1:])
        conv_g = state["conv"].reshape(g, a, *state["conv"].shape[1:])
        val = active[:, None] if active is not None else None

        def group(x, inp):
            gp, s_ssm, s_conv = inp[0], inp[1], inp[2]
            kv = inp[3:]

            def inner(x, i2):
                p, s1, s2 = i2
                with jax.named_scope("ssm"):
                    x, s1, s2 = C.mamba_decode_block(
                        cfg, p["mamba"], x, s1, s2, valid=val
                    )
                return x, (s1, s2)
            x, (s_ssm, s_conv) = jax.lax.scan(inner, x, (gp, s_ssm, s_conv))
            with jax.named_scope("attention"):
                x, kv = attn_dec(params["shared_attn"], x, kv)
            with jax.named_scope("mlp"):
                x = mlp_dec(params["shared_mlp"], x)
            return x, (s_ssm, s_conv) + kv

        with jax.named_scope("layers"):
            x, out = jax.lax.scan(
                group, x,
                (params["groups"], ssm_g, conv_g)
                + tuple(state[k] for k in kv_keys),
            )
        ssm, conv = out[0], out[1]
        state = {
            **state,
            "ssm": ssm.reshape(cfg.n_layers, *ssm.shape[2:]),
            "conv": conv.reshape(cfg.n_layers, *conv.shape[2:]),
            **dict(zip(kv_keys, out[2:])),
        }
    elif fam == "vlm":
        def group(x, inp):
            gp, cp, ck, cv, xk, xv = inp

            def inner(x, i2):
                p, ck1, cv1 = i2
                x, (ck1, cv1) = attn_dec(p["attn"], x, (ck1, cv1))
                x = mlp_dec(p["mlp"], x)
                return x, (ck1, cv1)
            x, (ck, cv) = jax.lax.scan(inner, x, (gp, ck, cv))
            # cross-attention to static vision K/V
            b = x.shape[0]
            hd = cfg.head_dim_
            pa = cp["attn"]
            xn = C.norm(cfg, pa["ln"], x)
            q = C.dense(xn, pa["wq"]).reshape(b, cfg.n_heads, hd)
            o = ops.attention_decode(
                q, xk, xv, jnp.asarray(cfg.n_vision_tokens, jnp.int32)
            )
            x = x + jnp.tanh(pa["gate"]) * C.dense(o.reshape(b, -1), pa["wo"])
            x = mlp_dec(cp["mlp"], x)
            return x, (ck, cv)

        x, (ks, vs) = jax.lax.scan(
            group, x,
            (params["groups"], params["cross"], state["k"], state["v"],
             state["xk"], state["xv"]),
        )
        state = {**state, "k": ks, "v": vs}
    else:
        raise ValueError(fam)

    with jax.named_scope("lm_head"):
        x = C.norm(cfg, params["ln_f"], x).astype(cfg.dtype_())
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = C.dense(x, w)
    with jax.named_scope("kv_pool"):
        if active is not None and pos.ndim == 1:
            state = {**state, "pos": pos + active.astype(jnp.int32)}
        else:
            state = {**state, "pos": pos + 1}
        if snap_every and "snap_table" in state and pos.ndim == 1:
            act = active if active is not None else jnp.ones_like(pos, bool)
            state = _snap_capture(state, state["pos"], act, snap_every)
    return logits, state


def prefill_chunk(
    cfg: ArchConfig, params, state, toks: jax.Array,   # (B, C) int32
    width: jax.Array,                                  # () or (B,) int32
    *, active: Optional[jax.Array] = None,             # (B,) bool
    cow: bool = False, snap_every: int = 0, logits_all: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Ingest up to C prompt tokens per row in one step.

    Row b's real tokens are ``toks[b, :width[b]]`` at absolute positions
    ``pos[b] .. pos[b]+width[b]-1``; the rest of the chunk is padding and
    never touches caches (masked multi-position K/V writes, zeroed-``dt``
    SSD no-ops, dropped page writes).  Returns logits at each row's
    *last real* position — exactly what a ``decode_step`` fed that position
    would return — and the state with per-row ``pos`` advanced by
    ``width`` for active rows.  ``width == 1`` rows degenerate to a decode
    step, so decode-phase rows can ride along in a mixed batch.

    Both block families chunk for real: attention runs one (C, hd) query
    block per row (``ops.attention_prefill_chunk``), and Mamba blocks run
    one masked per-row-width SSD scan seeded with the carried state
    (``C.mamba_prefill_block`` over ``ops.ssd_prefill_chunk``) — B*C-row
    GEMMs and one scan instead of C sequential dispatches.  Single-token
    decode is the C=1 case of the same block, so the two regimes share
    one accumulation order instead of two recurrences kept in parity by
    hand.

    ``snap_every`` (trace-time constant; recurrent families with a
    snapshot store) captures the post-chunk SSM/conv state of every row
    whose chunk ends exactly at a page boundary.  A chunk that *crosses*
    a boundary without ending there records nothing for it — callers that
    need full boundary coverage (the prefix-sharing engine) clip chunk
    widths to end at boundaries.

    ``logits_all=True`` (trace-time constant; the speculative-decoding
    verifier) returns logits at *every* chunk position — ``(B, C, V)``
    instead of ``(B, V)`` at the last real position.  In-chunk causality
    makes slot ``j``'s logits exact whenever slots ``0..j`` hold true
    tokens, which is precisely the prefix the greedy accept rule keeps.

    Requires ``per_row_pos`` decode state.  Sliding-window archs need the
    paged layout: the contiguous ring cache recycles slots the in-chunk
    queries still read.
    """
    pos = state["pos"]
    if pos.ndim != 1:
        raise ValueError("prefill_chunk needs per_row_pos=True decode state")
    paged = "block_table" in state
    quant = paged and "ksc" in state    # trace-time: int8 KV pools
    b, c = toks.shape
    uses_attn = cfg.family in ("dense", "moe", "hybrid", "vlm")
    if cfg.window and not paged and uses_attn:
        raise NotImplementedError(
            "chunked prefill with a sliding window needs layout='paged': "
            "the contiguous ring cache overwrites slots the in-chunk "
            "queries still read"
        )
    if active is None:
        active = jnp.ones((b,), bool)
    width = jnp.clip(
        jnp.broadcast_to(jnp.asarray(width, jnp.int32).reshape(-1), (b,)),
        1, c,
    )
    # named scopes as in decode_step
    with jax.named_scope("embed"):
        x = params["embed"][toks].astype(_residual_dtype(cfg))  # (B, C, d)
        offs = jnp.arange(c, dtype=jnp.int32)[None, :]
        posmat = pos[:, None] + offs                       # (B, C) positions
        valid = active[:, None] & (offs < width[:, None])  # (B, C) real

    if paged:
        from repro.serving import pager as PG

        # copy-on-write at the chunk's first position: shared blocks are
        # a page-aligned prefix of the row, so only position ``pos`` can
        # land in one (later in-chunk positions fall in the same — now
        # private — page or in fresh blocks mapped below)
        state, pstate, bt = _paged_cow(state, pos, active, cow=cow)
        # map every block the chunk touches up front (multi-page-per-step;
        # admission-time reservation guarantees the pops succeed)
        pstate, bt = PG.alloc_range(
            pstate, bt, pos, pos + width - 1, active,
            page_size=state["kp"].shape[2], max_chunk=c,
        )
        state = _paged_commit(state, pstate, bt)

    def attn_chunk(p, x, kv):
        # ``kv`` mirrors decode_step: (ck, cv) or quantized
        # (ck, cv, ksc, vsc) per-layer cache tuple
        hkv, hd = cfg.n_kv_heads, cfg.head_dim_
        xn = C.norm(cfg, p["ln"], x)
        q = C.dense(xn, p["wq"], p.get("bq")).reshape(b, c, cfg.n_heads, hd)
        k_new = C.dense(xn, p["wk"], p.get("bk")).reshape(b, c, hkv, hd)
        v_new = C.dense(xn, p["wv"], p.get("bv")).reshape(b, c, hkv, hd)
        cos, sin = C.rope_freqs(cfg, posmat)           # (B, C, hd/2)
        q = C.apply_rope(q, cos, sin)
        k_new = C.apply_rope(k_new, cos, sin)
        if paged:
            from repro.serving import pager as PG

            bt = state["block_table"]
            if quant:
                ck, cv, ksc, vsc = kv
                ck, ksc = PG.write_page_chunk_quant(
                    ck, ksc, k_new, bt, pos, width, active
                )
                cv, vsc = PG.write_page_chunk_quant(
                    cv, vsc, v_new, bt, pos, width, active
                )
                o = ops.attention_prefill_chunk(
                    q, ck, cv, pos, width, block_table=bt,
                    window=cfg.window, kv_scales=(ksc, vsc),
                )
                kv = (ck, cv, ksc, vsc)
            else:
                ck, cv = kv
                ck = PG.write_page_chunk(ck, k_new, bt, pos, width, active)
                cv = PG.write_page_chunk(cv, v_new, bt, pos, width, active)
                o = ops.attention_prefill_chunk(
                    q, ck, cv, pos, width, block_table=bt, window=cfg.window
                )
                kv = (ck, cv)
        else:
            ck, cv = kv
            ck = _cache_update_chunk(ck, k_new, posmat, valid)
            cv = _cache_update_chunk(cv, v_new, posmat, valid)
            o = ops.attention_prefill_chunk(q, ck, cv, pos, width)
            kv = (ck, cv)
        return x + C.dense(o.reshape(b, c, -1), p["wo"]), kv

    def mlp_chunk(p, x):
        xn = C.norm(cfg, p["ln"], x)
        h = jax.nn.silu(C.dense(xn, p["wg"])) * C.dense(xn, p["wi"])
        return x + C.dense(h, p["wo"])

    def mamba_chunk(p, x, s_ssm, s_conv):
        # one chunked SSD call per block: the carried state seeds the scan
        # and padding positions are algebraic no-ops (zeroed dt, width-
        # bounded conv gather), so per-row widths can't corrupt the carry
        return C.mamba_prefill_block(cfg, p, x, s_ssm, s_conv, valid)

    kk, vk = ("kp", "vp") if paged else ("k", "v")
    kv_keys = (kk, vk) + (("ksc", "vsc") if quant else ())

    fam = cfg.family
    if fam in ("dense", "moe"):
        def body(x, inp):
            p, kv = inp[0], inp[1:]
            with jax.named_scope("attention"):
                x, kv = attn_chunk(p["attn"], x, kv)
            with jax.named_scope("mlp"):
                x = (C.moe_block(cfg, p["moe"], x) if "moe" in p
                     else mlp_chunk(p["mlp"], x))
            return x, kv
        with jax.named_scope("layers"):
            x, kv_out = jax.lax.scan(
                body, x,
                (params["layers"],) + tuple(state[k] for k in kv_keys),
            )
        state = {**state, **dict(zip(kv_keys, kv_out))}
    elif fam == "ssm":
        def body(x, inp):
            p, s_ssm, s_conv = inp
            with jax.named_scope("ssm"):
                x, s_ssm, s_conv = mamba_chunk(p["mamba"], x, s_ssm, s_conv)
            return x, (s_ssm, s_conv)
        with jax.named_scope("layers"):
            x, (ssm, conv) = jax.lax.scan(
                body, x, (params["layers"], state["ssm"], state["conv"])
            )
        state = {**state, "ssm": ssm, "conv": conv}
    elif fam == "hybrid":
        g = cfg.n_layers // cfg.attn_every
        a = cfg.attn_every
        ssm_g = state["ssm"].reshape(g, a, *state["ssm"].shape[1:])
        conv_g = state["conv"].reshape(g, a, *state["conv"].shape[1:])

        def group(x, inp):
            gp, s_ssm, s_conv = inp[0], inp[1], inp[2]
            kv = inp[3:]

            def inner(x, i2):
                p, s1, s2 = i2
                with jax.named_scope("ssm"):
                    x, s1, s2 = mamba_chunk(p["mamba"], x, s1, s2)
                return x, (s1, s2)
            x, (s_ssm, s_conv) = jax.lax.scan(inner, x, (gp, s_ssm, s_conv))
            with jax.named_scope("attention"):
                x, kv = attn_chunk(params["shared_attn"], x, kv)
            with jax.named_scope("mlp"):
                x = mlp_chunk(params["shared_mlp"], x)
            return x, (s_ssm, s_conv) + kv

        with jax.named_scope("layers"):
            x, out = jax.lax.scan(
                group, x,
                (params["groups"], ssm_g, conv_g)
                + tuple(state[k] for k in kv_keys),
            )
        ssm, conv = out[0], out[1]
        state = {
            **state,
            "ssm": ssm.reshape(cfg.n_layers, *ssm.shape[2:]),
            "conv": conv.reshape(cfg.n_layers, *conv.shape[2:]),
            **dict(zip(kv_keys, out[2:])),
        }
    else:
        raise NotImplementedError(
            f"prefill_chunk: unsupported family {fam!r}"
        )

    # logits at each row's last real position (gather-then-norm: the final
    # norm and head are position-wise, so this equals the decode_step there)
    with jax.named_scope("lm_head"):
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        if logits_all:
            # verifier path: every chunk position's logits (B, C, V);
            # padding positions carry garbage the caller masks by width
            h = C.norm(cfg, params["ln_f"], x).astype(cfg.dtype_())
            logits = C.dense(h, w)
        else:
            last = jnp.take_along_axis(
                x, (width - 1)[:, None, None], axis=1
            )[:, 0]
            h = C.norm(cfg, params["ln_f"], last).astype(cfg.dtype_())
            logits = C.dense(h, w)
    with jax.named_scope("kv_pool"):
        state = {**state, "pos": pos + jnp.where(active, width, 0)}
        if snap_every and "snap_table" in state:
            state = _snap_capture(state, state["pos"], active, snap_every)
    return logits, state


def prefill(
    cfg: ArchConfig, params, tokens: jax.Array,
    *, vision: Optional[jax.Array] = None,
) -> jax.Array:
    """Prefill = forward pass producing last-position logits (caches omitted
    in the benchmarked path; decode cells measure steady-state decode)."""
    h = forward(cfg, params, tokens, vision=vision, remat=False)
    return lm_logits(cfg, params, h[:, -1:, :])[:, 0]


def reset_decode_rows(
    cfg: ArchConfig, state: Dict[str, jax.Array], mask: jax.Array,  # (B,) bool
    start: jax.Array = 0,                                  # () or (B,) int32
) -> Dict[str, jax.Array]:
    """Zero the decode caches of the rows selected by ``mask``.

    The slot-refill path of the serving engine: a finished row's caches are
    reset in place (no retracing, no reallocation) before a queued request
    is admitted into it.  Requires ``per_row_pos`` state — with a scalar
    ``pos`` the rows share a clock and cannot be reset independently.

    ``start`` places the reset rows' decode clock (prefix-sharing
    admission: positions below ``start`` are already cached in pages the
    engine maps via ``pager.share_prefix`` right after this reset, so
    prefill resumes at the first unshared token instead of position 0).
    """
    if state["pos"].ndim != 1:
        raise ValueError(
            "reset_decode_rows needs per_row_pos=True decode state"
        )
    # drf_* is the hybrid_ssm drafter's private recurrent state
    # (repro.serving.drafter): batch axis 1, zeroed like ssm/conv
    known = {"k", "v", "ssm", "conv", "xk", "xv", "drf_ssm", "drf_conv"}
    paged_keys = {"kp", "vp", "ksc", "vsc", "block_table", "page_free",
                  "page_top", "page_rc"}
    snap_keys = {"snap_ssm", "snap_conv", "snap_table", "snap_free",
                 "snap_top", "snap_rc"}
    host_keys = {"hkp", "hvp", "hksc", "hvsc", "host_table", "host_free",
                 "host_top", "host_rc"}
    hsnap_keys = {"hsnap_ssm", "hsnap_conv", "hsnap_table", "hsnap_free",
                  "hsnap_top", "hsnap_rc"}
    unknown = (set(state) - known - paged_keys - snap_keys - host_keys
               - hsnap_keys - {"pos", "drf_pos"})
    if unknown:
        # fail loudly: a silently-skipped cache key would leak the previous
        # request's state into the slot's next occupant
        raise ValueError(
            f"reset_decode_rows: unhandled decode-state keys {sorted(unknown)}"
            " — declare their batch axis here before serving with them"
        )
    out = dict(state)
    out["pos"] = jnp.where(mask, jnp.asarray(start, jnp.int32), state["pos"])
    if "drf_pos" in state:
        # the drafter's ingestion clock resets with the row's decode clock
        out["drf_pos"] = jnp.where(
            mask, jnp.asarray(start, jnp.int32), state["drf_pos"]
        )
    if "block_table" in state:
        # paged layout: a reset row *releases* its pages (the pool is global
        # and is never zeroed — a recycled page is fully overwritten by its
        # next owner before any masked-in read can see it); pages still
        # referenced by a prefix-sharing peer stay resident (refcounts)
        from repro.serving import pager as PG

        pstate, bt = PG.release_rows(
            PG.PagerState(state["page_free"], state["page_top"],
                          state["page_rc"]),
            state["block_table"], mask,
        )
        out["block_table"] = bt
        out["page_free"], out["page_top"] = pstate.free, pstate.top
        out["page_rc"] = pstate.rc
    if "snap_table" in state:
        # snapshot slots are released with their rows exactly like pages:
        # refs drop, slots still held by a prefix-sharing peer stay
        # resident, and the pools are never zeroed (a recycled slot is
        # fully overwritten at its next boundary capture before any
        # restore can read it)
        from repro.serving import pager as PG

        sstate, stbl = PG.release_rows(
            PG.PagerState(state["snap_free"], state["snap_top"],
                          state["snap_rc"]),
            state["snap_table"], mask,
        )
        out["snap_table"] = stbl
        out["snap_free"], out["snap_top"] = sstate.free, sstate.top
        out["snap_rc"] = sstate.rc
    if "host_table" in state:
        # a row cancelled *while spilled* drains through the same path:
        # its host slots are released exactly like device pages (host
        # copies are private — rc == 1 — so they always return to the
        # host free list; the pools are never zeroed)
        from repro.serving import pager as PG

        hstate, ht = PG.release_rows(
            PG.PagerState(state["host_free"], state["host_top"],
                          state["host_rc"]),
            state["host_table"], mask,
        )
        out["host_table"] = ht
        out["host_free"], out["host_top"] = hstate.free, hstate.top
        out["host_rc"] = hstate.rc
    if "hsnap_table" in state:
        from repro.serving import pager as PG

        hs, hstbl = PG.release_rows(
            PG.PagerState(state["hsnap_free"], state["hsnap_top"],
                          state["hsnap_rc"]),
            state["hsnap_table"], mask,
        )
        out["hsnap_table"] = hstbl
        out["hsnap_free"], out["hsnap_top"] = hs.free, hs.top
        out["hsnap_rc"] = hs.rc
    for key in known & set(state):
        v = state[key]
        # batch axis: (layers/groups, B, ...) except the VLM self-attn cache,
        # which is (groups, per, B, ...)
        axis = 2 if cfg.family == "vlm" and key in ("k", "v") else 1
        shape = [1] * v.ndim
        shape[axis] = mask.shape[0]
        out[key] = jnp.where(
            mask.reshape(shape), jnp.zeros((), v.dtype), v
        )
    return out


def prefill_vlm_cross_cache(cfg: ArchConfig, params, vision, state):
    """Fill the static cross K/V from vision embeddings (VLM serving)."""
    g = cfg.n_layers // cfg.cross_attn_every
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_

    def per_group(cp):
        pa = cp["attn"]
        src = C.norm(cfg, pa["ln"], vision)
        k = C.dense(src, pa["wk"]).reshape(*vision.shape[:2], hkv, hd)
        v = C.dense(src, pa["wv"]).reshape(*vision.shape[:2], hkv, hd)
        return k, v

    xk, xv = jax.vmap(per_group)(params["cross"])
    return {**state, "xk": xk, "xv": xv}
