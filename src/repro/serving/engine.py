"""Device-side continuous-batching serving engine.

The paper's §4.3 lesson is that a *partial* port pays for every boundary
crossing between the ported device domain and the host orchestrator.  The
previous serving loop was exactly that pathology: per-row Python decided
prompt-vs-generated feeding and completion with an ``int()`` host sync per
row per decode step.  Here the whole control state lives on-device:

  * ``SlotState`` — per-row token buffer, progress counters, and phase
    flags as fixed-shape device arrays (the prompt/generated distinction is
    a *comparison*, not a branch: generated tokens are written into the
    same buffer the prompt occupies, so feeding is one gather).
  * ``engine_step`` — one fused jit step: token selection, decode, greedy
    sampling, generated-token scatter, done-detection — all ``jnp`` ops.
    ``steps_per_sync`` steps run back-to-back inside one jit call, so
    there is (at most) one host sync per *batch of steps*.
  * slot refill — a jitted masked-write ``admit`` with fixed shapes: new
    requests enter free rows without retracing anything.
  * KV layout — ``layout="paged"`` swaps the dense per-row cache slab for
    a page pool + block table + device-side free list (contract in
    ``repro.serving.pager``).  Admission reserves pages (host arithmetic,
    no sync), decode allocates them lazily on first write, harvest
    releases them — so resident KV tracks live tokens, and the pool may be
    much smaller than ``batch x max_len``.  All of it is the same
    masked-write, fixed-shape discipline: nothing retraces.
  * chunked prefill — ``prefill_chunk=C`` ingests up to C prompt tokens
    per step (``model.prefill_chunk`` + ``ops.attention_prefill_chunk``),
    so TTFT stops scaling linearly in prompt length.  The scheduler is a
    *host mirror*: per-row progress is a deterministic function of
    (prompt_len, total_len, steps run), so choosing prefill-vs-decode
    steps, chunk widths, TTFT stamps and ingestion counts never needs a
    device sync.  Decode-phase rows ride along in prefill steps with
    width 1; both jitted entry points stay at cache size 1.
  * prefix sharing — ``prefix_sharing=True`` (paged layout only) keeps a
    host-side index of page-aligned prompt chunks (chained hashes, exact
    token verification — deterministic, no device sync).  Admission maps
    a matching resident row's prompt pages into the new row's block
    table by *donor slot id* (``pager.share_prefix`` bumps refcounts on
    device; the host never needs physical page ids) and starts chunked
    prefill at the first unshared token.  Writes into a still-shared
    page copy-on-write to a private page inside the jitted step
    (``pager.cow_on_write``), so ``_admit``/``_prefill``/``_step_n`` all
    stay at jit cache size 1 and outputs are token-identical to the
    no-sharing engine.
  * recurrent-state snapshots — sharing is first-class for the recurrent
    families too (ssm, and the hybrid family's Mamba blocks): their
    decode state holds a page-boundary snapshot store (per-(row,
    boundary) SSM+conv slots, same refcounted allocator as KV pages —
    ``repro.serving.pager`` documents the contract).  Steps that end
    exactly at a boundary capture the post-step state (the engine clips
    prefill chunk widths so every boundary is an endpoint); admission of
    a matching prompt restores the donor's boundary state
    (``lm.restore_snapshots``) and resumes prefill at the first unshared
    token — the recurrence is *restored*, never skipped.  Shared depth is
    capped below the prompt's last token so the resume point is always a
    snapshotted boundary (recurrent sharing therefore never needs CoW).
  * speculative decoding — ``config.spec`` (a ``SpecConfig``) turns the
    decode phase into draft-and-verify: a drafter
    (``repro.serving.drafter`` — n-gram prompt lookup, or the hybrid
    family's own Mamba layers) proposes K tokens per row, and one
    ``model.prefill_chunk`` call at width K+1 scores every slot
    (``logits_all=True``) against the paged cache.  Each row keeps its
    leading run of drafts that match the verifier's own argmax and
    advances by the per-row accepted length — the same non-dividing-
    width masking chunked prefill already uses — so acceptance is
    greedy and *token-identical to plain decode by construction* (every
    emitted token is the verifier's argmax).  Rejected suffixes roll
    back: attention families rewind ``pos`` and release tail pages
    (``pager.release_tail``); recurrent families score on a discarded
    state and re-advance the original by the exact accepted width
    (nothing to roll back).  All of it lives in one jitted ``_spec_n``
    at cache size 1, with a device-side accept counter riding the
    harvest sync.
  * pressure — the engine survives a pool smaller than its working set:
    when the queue head cannot reserve pages, the host-mirror scheduler
    preempts victim rows (lowest priority, then least progress),
    spilling their pages — and, for recurrent families, their snapshot
    slots — to a host-side tier through a jitted ``_spill`` (two-tier
    contract in ``repro.serving.pager``) and restoring them when pages
    free up.  ``cancel()`` and per-request deadlines drain rows through
    the same jitted release path at the next harvest, and
    ``repro.serving.faults.FaultPlan`` scripts deterministic pressure
    (pool exhaustion, cancels, deadline storms, poisoned rows) against
    the harvest-cycle clock for the CI harness.

Supported families: dense / moe / ssm / hybrid (everything whose decode
state supports per-row positions; VLM cross-caches would additionally need
a per-row vision prefill at admission).

MoE caveat: with capacity dropping (``capacity_factor`` below no-drop) a
row's output depends on which other rows share its decode batch — standard
MoE serving semantics, not an engine artifact.  Token-exact parity with
isolated decode holds when ``capacity_factor >= n_experts``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, NamedTuple, Optional, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.serving.config import (
    CacheConfig, EngineConfig, from_kwargs, validate_configs,
)
from repro.serving.drafter import make_drafter
from repro.serving.faults import FaultPlan
from repro.serving.queue import Request, RequestQueue

# Host spans of one ``ServingEngine.step`` cycle, as
# ``jax.profiler.TraceAnnotation`` names: the whole cycle, then its parts
# in order (admission, each chunked-prefill dispatch, the fused decode
# dispatch, the one blocking readback, the release dispatch).  Outside a
# profiler trace each costs one flag check.
SPANS = ("engine.step", "engine.refill", "engine.prefill", "engine.decode",
         "engine.harvest", "engine.release")


class SlotState(NamedTuple):
    """Per-row serving control state — all device arrays, fixed shapes."""

    tokens: jax.Array      # (B, max_len) int32: prompt then generated tokens
    prompt_len: jax.Array  # (B,) int32
    total_len: jax.Array   # (B,) int32: prompt_len + max_new_tokens
    progress: jax.Array    # (B,) int32: tokens fed to the model so far
    active: jax.Array      # (B,) bool: row currently serving a request
    rng: jax.Array         # (B, 2) uint32: per-row PRNG key (sampling)


def init_slots(batch: int, max_len: int) -> SlotState:
    return SlotState(
        tokens=jnp.zeros((batch, max_len), jnp.int32),
        prompt_len=jnp.ones((batch,), jnp.int32),
        total_len=jnp.ones((batch,), jnp.int32),
        progress=jnp.zeros((batch,), jnp.int32),
        active=jnp.zeros((batch,), bool),
        rng=jnp.zeros((batch, 2), jnp.uint32),
    )


def _sample(logits, slots: SlotState, wpos, *, temperature: float,
            top_k: int):
    """Next-token choice.

    ``temperature``/``top_k`` are trace-time constants (engine config), so
    the greedy path compiles to exactly the pre-sampling graph.  Each
    sampled token's subkey is ``fold_in(admission key, position)`` —
    ``wpos`` is where the token lands — so a row's token stream depends
    only on its admission-time key and the positions themselves: refills,
    batch composition, ``steps_per_sync`` and the prefill chunk schedule
    (which changes how many *steps* reach a given position) cannot
    perturb it.  The key is never consumed, so ``slots.rng`` is carried
    unchanged.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sub = jax.vmap(jax.random.fold_in)(slots.rng, wpos)
    lg = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    return jax.vmap(jax.random.categorical)(sub, lg).astype(jnp.int32)


def engine_step(model: Model, params, mstate, slots: SlotState,
                *, temperature: float = 0.0, top_k: int = 0,
                chunk: int = 1, cow: bool = False, snap_every: int = 0):
    """One decode (or chunked-prefill) step for every row — no host
    interaction.

    ``chunk == 1`` (the decode step): row b feeds ``tokens[b,
    progress[b]]``; because generated tokens are scattered into the buffer
    as they are produced, this single gather covers both the prompt phase
    and the generate phase.  A row is done after the step that produces
    its last generated token (``progress`` reaches ``total_len - 1``:
    position t's feed predicts position t+1, and positions ``prompt_len ..
    total_len-1`` are generated).  Inactive rows still occupy their lane
    (fixed shapes) but never advance, never write their caches, and —
    under the paged KV layout — never allocate pages (the ``active`` mask
    flows down through ``decode_step``).

    ``chunk > 1`` (the prefill step): prompt-phase rows feed up to
    ``chunk`` prompt tokens at once through ``model.prefill_chunk`` —
    per-row width ``clip(prompt_len - progress, 1, chunk)``, so the chunk
    never crosses into generated positions and the *last* prefill chunk
    ends exactly at ``prompt_len - 1``, whose logits produce the first
    generated token.  Decode-phase rows ride along with width 1 (their
    gather covers the generated buffer), so a mixed batch needs no second
    dispatch point.  Everything else — sampling, token scatter,
    done-detection — is the same arithmetic with a per-row stride.

    ``snap_every`` (trace-time constant; recurrent snapshot engines) does
    two things: chunk widths are additionally clipped so no chunk crosses
    a page boundary without *ending* on it (every boundary becomes a step
    endpoint, so every boundary state gets captured — the availability
    invariant the host-side prefix index relies on), and the model steps
    capture/carry the snapshot store.  The host mirror in ``step()``
    replays the same clip.

    The feed gather runs under the ``embed`` name scope and sampling,
    the token scatter and done-detection under ``sample`` (the model
    step names its own parts).
    """
    b, max_len = slots.tokens.shape
    if chunk > 1:
        with jax.named_scope("embed"):
            limit = jnp.full((b,), chunk, jnp.int32)
            if snap_every:
                limit = jnp.minimum(
                    limit, snap_every - slots.progress % snap_every
                )
            width = jnp.clip(slots.prompt_len - slots.progress, 1, limit)
            gidx = jnp.clip(
                slots.progress[:, None]
                + jnp.arange(chunk, dtype=jnp.int32)[None, :],
                0, max_len - 1,
            )
            toks = jnp.take_along_axis(slots.tokens, gidx, axis=1)
        logits, mstate = model.prefill_chunk(params, mstate, toks, width,
                                             active=slots.active, cow=cow,
                                             snap_every=snap_every)
        stride = width
    else:
        with jax.named_scope("embed"):
            feed_idx = jnp.clip(slots.progress, 0, max_len - 1)
            tok = jnp.take_along_axis(
                slots.tokens, feed_idx[:, None], axis=1
            )[:, 0]
        logits, mstate = model.decode_step(params, mstate, tok,
                                           active=slots.active, cow=cow,
                                           snap_every=snap_every)
        stride = jnp.ones((b,), jnp.int32)

    with jax.named_scope("sample"):
        wpos = slots.progress + stride
        nxt = _sample(logits, slots, wpos, temperature=temperature,
                      top_k=top_k)
        # scatter the sampled token where the next feed position is
        # generated
        writes = slots.active & (wpos >= slots.prompt_len) & (wpos < max_len)
        col = jax.lax.broadcasted_iota(jnp.int32, (b, max_len), 1)
        tokens = jnp.where(
            writes[:, None] & (col == wpos[:, None]), nxt[:, None],
            slots.tokens
        )
        progress = slots.progress + stride * slots.active.astype(jnp.int32)
        active = slots.active & (progress < slots.total_len - 1)
    return mstate, SlotState(
        tokens=tokens,
        prompt_len=slots.prompt_len,
        total_len=slots.total_len,
        progress=progress,
        active=active,
        rng=slots.rng,
    )


class RequestHandle(int):
    """What ``submit`` returns: the request id, plus the request-scoped
    surface (``handle.cancel()``, ``handle.rid``).

    Subclasses ``int`` so every pre-handle idiom keeps working — handles
    index ``outputs``/``ttft`` dicts, format into messages, and compare
    equal to the raw id.  The engine reference only powers the
    convenience methods; the id alone remains a full citizen everywhere
    the engine API takes one.
    """

    def __new__(cls, rid: int, engine: "ServingEngine"):
        self = super().__new__(cls, rid)
        self._engine = engine
        return self

    @property
    def rid(self) -> int:
        """The request id as a plain ``int``."""
        return int(self)

    def cancel(self) -> bool:
        """Cancel this request (``ServingEngine.cancel`` semantics)."""
        return self._engine.cancel(self.rid)


class ServingEngine:
    """Fixed-shape continuous-batching engine over a ``Model``.

    >>> eng = ServingEngine(model, params, batch=4, max_len=64,
    ...                     cache=CacheConfig(layout="paged"),
    ...                     config=EngineConfig(prefill_chunk=8))
    >>> h = eng.submit([3, 17, 5], max_new_tokens=16)   # RequestHandle
    >>> outs = eng.run()          # {rid: np.ndarray of generated tokens}

    Configuration is two frozen objects (``repro.serving.config``):
    ``cache=CacheConfig(...)`` shapes the decode state (KV layout, page
    pool, snapshot store, host spill tier) and ``config=EngineConfig(...)``
    drives the loop (scheduling, sampling, speculation).  The raw kwargs
    of earlier revisions (``layout=``, ``page_size=``, ``prefill_chunk=``,
    …) still work through one adapter — ``config.from_kwargs`` — which
    emits a ``DeprecationWarning`` per call site; mixing both styles is a
    ``TypeError``.  All validation messages are unchanged from the
    kwarg era (they moved into the config constructors and
    ``validate_configs``).

    ``config.spec=SpecConfig(k=K, drafter=...)`` enables speculative
    decoding (greedy-only; requires ``prefill_chunk >= 2`` and
    ``temperature == 0``): each fused decode interval drafts K tokens
    per row and verifies them through the chunked-prefill path in one
    jitted step, advancing every row by its accepted length.  Outputs
    are token-identical to plain greedy decode (module docstring has the
    argument); ``stats()`` gains ``spec_proposed`` / ``spec_accepted`` /
    ``spec_emitted`` / ``spec_accept_rate``.  ``drafter="prompt_lookup"``
    works for every supported family; ``drafter="hybrid_ssm"`` (the
    hybrid family's Mamba layers as a weight-shared draft model) needs
    ``family == "hybrid"`` and is incompatible with ``prefix_sharing``
    (snapshot restore rebuilds the model's recurrence, not the drafter's
    private state).

    ``layout="paged"`` swaps the KV cache for the page-pool representation
    (``repro.serving.pager``): admission reserves ``ceil((total_len-1)/
    page_size)`` pages per request (host-side accounting — no device sync),
    pages are *allocated* lazily as tokens are written, and a finished
    row's pages return to the pool at harvest, before its slot is even
    refilled.  Resident KV therefore scales with live tokens; ``n_pages``
    may be far below the contiguous ``batch * max_len / page_size``.

    ``temperature > 0`` enables on-device sampling (optionally top-k
    truncated); each admitted request gets its own PRNG key derived from
    the engine seed (host-side draw — the admission path stays sync-free),
    so outputs are reproducible per request regardless of batch
    composition.  The default (0) is greedy argmax, byte-identical to the
    pre-sampling engine.

    ``prefill_chunk=C`` (default 1 = token-by-token) turns prompt
    ingestion into chunked multi-token steps: a row with R prompt tokens
    left feeds ``min(C, R)`` of them in one fused step, so a P-token
    prompt costs ``ceil(P/C)`` steps instead of P.  Outputs are
    token-identical to the unchunked path; per-request ``ttft`` (seconds
    to first generated token, stamped at the harvest sync) and
    ``prompt_tokens`` are tracked either way.  MoE caveat: with capacity
    dropping, chunked steps route B*C tokens where decode routes B, so
    drops — and therefore tokens — can differ from the unchunked path;
    parity holds at ``capacity_factor >= n_experts`` (see module
    docstring).  Sliding-window archs need ``layout="paged"`` for
    chunking (absolute positions; the contiguous ring recycles slots the
    chunk still reads).

    ``prefix_sharing=True`` (paged layout only): a new request whose
    prompt starts with page-aligned chunks already written by a resident
    row maps that row's pages instead of recomputing them — prefill
    starts at the first unshared token, the shared pages' refcounts keep
    them alive past the donor's completion, and the one write that can
    land in a shared page (the re-fed last prompt token of a fully
    shared prompt) copies-on-write to a private page.  Outputs are
    token-identical to the no-sharing engine; what changes is TTFT and
    resident KV bytes (shared pages are resident once, not per row).
    Recurrent decode state (ssm, and the hybrid family's Mamba blocks)
    shares through the page-boundary snapshot store: admission restores
    the donor's captured SSM/conv state at the last shared boundary
    instead of re-running the recurrence, with shared depth capped below
    the prompt's final token so the resume point is always a snapshotted
    boundary (recurrent sharing never CoWs; see the module docstring).
    Snapshot engines clip prefill chunk widths to end at page
    boundaries, so every boundary state is captured as it is first
    reached.  MoE caveat as for chunked prefill: sharing changes which
    tokens batch into a routing step, so parity needs
    ``capacity_factor >= n_experts``.  Admission reserves the worst-case
    page count *without* subtracting shared pages (plus the one CoW
    spare for attention families): a donor may finish first, leaving the
    sharer sole holder, so the conservative ledger is what keeps
    alloc-on-write sync-free and never dry.  The snapshot-slot pool is
    sized to the same worst case at construction (every row can
    snapshot every boundary it can reach), so it needs no ledger at all.

    Scheduler contract (preemption / deadlines / cancellation).
    Admission orders the queue by (priority desc, deadline budget asc,
    arrival asc).  When the head cannot reserve its worst-case pages
    under the ledger, the scheduler spills victims — resident rows of
    *strictly lower* priority, lowest priority first, then least
    progress (least work lost), then oldest — through the jitted
    ``_spill``: a spill moves the row's KV pages (and snapshot slots)
    to the host tier, keeps its ``SlotState`` lane and live recurrent
    state in place, and returns its reservation to the pool.  Victims
    are committed only if they actually admit the head (no thrashing
    spills).  Spilled rows restore (highest priority, then oldest,
    first) as soon as their worst-case reservation fits again — the
    reservation gate is what guarantees the jitted restore's device
    pops never find the free list dry — deferring to a strictly-
    higher-priority queue head that could itself fit.  ``cancel(
    req_id)`` and deadline expiry (absolute time ``submit +
    deadline_ms``, measured against ``time.perf_counter`` — the
    monotonic host clock — at every harvest sync and every
    queued-request sweep) take effect at the next harvest: still-queued
    requests leave the queue immediately; resident, mid-prefill, and
    spilled rows drain through the jitted release path, surrendering
    pages and slots in every tier with no payload recorded.
    ``prefill_budget`` bounds chunked-prefill steps per cycle so a
    long prompt cannot monopolize a harvest interval (TTFT
    interference control); leftover prompt tokens continue next cycle
    or token-by-token inside the fused decode call.  A ``FaultPlan``
    (``fault_plan=`` or ``set_fault_plan``) scripts pool exhaustion,
    cancels, deadline storms, and poisoned rows against the
    harvest-cycle clock — injections ride the normal scheduler paths
    above, never a parallel code path.
    """

    def __init__(
        self,
        model: Model,
        params,
        *,
        batch: int,
        max_len: int,
        cache: Optional[CacheConfig] = None,
        config: Optional[EngineConfig] = None,
        fault_plan: Optional[FaultPlan] = None,
        **legacy,
    ) -> None:
        if legacy:
            if cache is not None or config is not None:
                raise TypeError(
                    "pass cache=CacheConfig(...) / config=EngineConfig(...) "
                    "or the legacy kwargs, not both"
                )
            # one adapter owns the kwarg->config translation; the
            # stacklevel points the DeprecationWarning at the caller's
            # construction site, not this frame
            cache, config = from_kwargs(_stacklevel=3, **legacy)
        cache = cache if cache is not None else CacheConfig()
        config = config if config is not None else EngineConfig()
        validate_configs(cache, config)
        if model.cfg.family not in ("dense", "moe", "ssm", "hybrid"):
            raise NotImplementedError(
                f"serving engine: unsupported family {model.cfg.family!r}"
            )
        if (config.prefill_chunk > 1 and model.cfg.window
                and cache.layout != "paged"
                and model.cfg.family in ("dense", "moe", "hybrid")):
            raise ValueError(
                "chunked prefill on a sliding-window arch needs "
                "layout='paged' (the contiguous ring cache recycles slots "
                "the in-chunk queries still read)"
            )
        steps_per_sync = config.steps_per_sync
        prefill_chunk = config.prefill_chunk
        page_size = cache.page_size
        self.model = model
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.cache = cache
        self.config = config
        self.spec = config.spec
        # flat attribute surface preserved from the kwarg era (tests and
        # benchmark drivers read these)
        self.steps_per_sync = steps_per_sync
        self.layout = cache.layout
        self.prefill_chunk = prefill_chunk
        self.prefix_sharing = config.prefix_sharing
        self.temperature = config.temperature
        self.top_k = config.top_k
        self.prefill_budget = config.prefill_budget
        self.queue = RequestQueue(max_len=max_len)

        host_spill = cache.host_spill
        if host_spill is None:
            # preemption only makes sense where there are pages to spill
            host_spill = cache.layout == "paged"
        # the engine's own construction dogfoods the typed config: the
        # snapshot store exists when asked for explicitly or implied by
        # prefix sharing on a recurrent family
        self._mstate = model.init_decode_state(
            batch, max_len, per_row_pos=True,
            cache=dataclasses.replace(
                cache,
                snapshots=cache.snapshots or config.prefix_sharing,
                host_spill=host_spill,
            ),
        )
        # speculative decoding: build the drafter before the jitted
        # closures (the prefill closure ingests for a stateful drafter);
        # its private recurrent state merges into the decode-state dict
        # so reset/spill/restore/donation treat it as lane state
        self._drafter = None
        if self.spec is not None:
            self._drafter = make_drafter(self.spec, model.cfg)
            if self._drafter.stateful:
                self._mstate = {
                    **self._mstate,
                    **self._drafter.init_state(batch),
                }
        # attention-free families have no pages regardless of the flag
        self._paged = "block_table" in self._mstate
        # recurrent families carry a page-boundary snapshot store exactly
        # when sharing is on (lm.init_decode_state adds it)
        self._snap = "snap_table" in self._mstate
        self._recurrent = model.cfg.family in ("ssm", "hybrid")
        self.page_size = page_size
        self.n_pages = (
            int(self._mstate["page_free"].shape[0]) if self._paged else 0
        )
        self.n_snap_slots = (
            int(self._mstate["snap_free"].shape[0]) if self._snap else 0
        )
        # host-side reservation ledger: worst-case pages per occupied row.
        # Guarantees alloc-on-write never finds the free list empty, so no
        # device sync is needed on the admission path.
        self._row_pages: List[int] = [0] * batch
        self._pages_reserved = 0
        self.peak_pages_in_use = 0
        self.peak_snaps_in_use = 0
        # two-tier pager (preemption): present exactly when the state has
        # a host tier (paged layout + a family with KV pages)
        self._spillable = "host_table" in self._mstate
        # spill mirrors: a spilled row keeps its SlotState lane (tokens,
        # progress, live recurrent state) — only pool residency and the
        # reservation move; _spill_need remembers the worst-case pages to
        # re-reserve at restore
        self._row_spilled: List[bool] = [False] * batch
        self._spill_need: List[int] = [0] * batch
        self.preemptions = 0
        self.restores = 0
        # every family shares: dense/moe through aliased KV pages, ssm
        # through restored state snapshots, hybrid through both
        self._share_eligible = self.prefix_sharing and (
            self._paged or self._snap
        )
        # host-side prefix index: chained chunk hash -> (slot, epoch).
        # Epochs invalidate entries when their slot's request is released;
        # matches are verified token-exact against the donor's prompt, so
        # a hash collision can never map the wrong pages.
        self._prefix_index: Dict[int, tuple] = {}
        self._slot_epoch: List[int] = [0] * batch
        self._slot_hashes: List[List[int]] = [[] for _ in range(batch)]
        self.shared_prompt_tokens = 0   # prompt tokens skipped via sharing
        self.cow_pages = 0              # CoW copies (host-predicted)

        # KV byte arithmetic is shape-only — freeze it here instead of
        # re-walking the state pytree on every stats()/resident-bytes call
        if self._paged:
            kp = self._mstate["kp"]
            stacks, _, page, hkv, hd = kp.shape
            self._kv_bytes_per_page = (
                2 * kp.dtype.itemsize * stacks * page * hkv * hd
            )
            self._contig_kv_bytes = 0
        else:
            self._kv_bytes_per_page = 0
            self._contig_kv_bytes = sum(
                self._mstate[key].dtype.itemsize
                * int(np.prod(self._mstate[key].shape))
                for key in ("k", "v", "xk", "xv") if key in self._mstate
            )

        # recurrent state is fixed per row: its bytes are shape arithmetic
        self._recurrent_bytes = sum(
            self._mstate[key].dtype.itemsize
            * int(np.prod(self._mstate[key].shape))
            for key in ("ssm", "conv") if key in self._mstate
        )

        self._slots = init_slots(batch, max_len)
        # per-request key *data* is derived host-side from (engine seed,
        # req_id) — no device round-trip on the admission path, and the
        # stream is a pure function of the request's identity, so
        # admission *order* (which priorities and preemption reshuffle)
        # cannot perturb any row's tokens
        self._seed = int(config.seed)
        # host mirror: which request occupies each row (None = free)
        self._slot_req: List[Optional[Request]] = [None] * batch
        # host mirror of per-row progress: the step schedule (chunk widths,
        # prompt-vs-decode phase) is a deterministic function of
        # (prompt_len, total_len, steps run), so the prefill scheduler and
        # the TTFT/ingestion accounting never need a device sync
        self._row_progress: List[int] = [0] * batch
        self.outputs: Dict[int, np.ndarray] = {}
        self.steps = 0          # decode steps executed (all rows per step)
        self.prefill_steps = 0  # chunked-prefill steps executed
        self.generated = 0      # tokens returned to callers
        self.prompt_tokens = 0  # prompt tokens ingested (host arithmetic)
        # slot use of the two step programs (host mirror): token slots the
        # chunked-prefill program ran (batch x prefill_chunk per dispatch)
        # and the positions live rows advanced in them; row-steps the fused
        # decode program ran (batch x steps_per_sync per call) and the
        # positions live rows advanced in them
        self.prefill_slots = self.prefill_positions = 0
        self.decode_row_steps = self.decode_positions = 0
        # speculation counters: host mirrors of the device accumulator
        # (refreshed at each harvest sync — never a dedicated round-trip)
        self.spec_proposed = 0  # verifiable draft positions scored
        self.spec_accepted = 0  # drafts that matched the verifier argmax
        self.spec_emitted = 0   # tokens emitted by spec steps (incl. bonus)
        self.ttft: Dict[int, float] = {}        # req_id -> seconds
        self._t_submit: Dict[int, float] = {}
        # SLO / cancellation ledgers (host mirror; enforcement happens at
        # refill for queued requests and at harvest for resident rows)
        self._deadline: Dict[int, float] = {}   # req_id -> absolute expiry
        self._cancel_req: Set[int] = set()      # resident, pending drain
        self._poison_req: Set[int] = set()
        self.cancelled: Set[int] = set()        # records (never completed)
        self.expired: Set[int] = set()
        self.poisoned: Set[int] = set()
        # fault-injection harness: harvest-cycle clock + hostage pages
        self.fault_plan = fault_plan
        self._cycle = 0
        self._fault_hold_pages = 0

        # the CoW pass only exists in traces that can ever share a page
        # (static per engine): non-sharing paged engines keep the plain
        # allocator's decode trace.  Recurrent sharing never writes into
        # a shared page (resume points sit on unshared boundaries), so
        # snapshot-only (ssm) engines skip the CoW pass too.
        cow = self._share_eligible and self._paged
        # snapshot capture + boundary-aligned chunk clipping only exist in
        # traces that own a snapshot store (static per engine)
        snap_every = page_size if self._snap else 0
        self._snap_every = snap_every

        def _step_n(params, mstate, slots, run):
            # ``run`` freezes rows for this fused call without touching
            # their lanes: a ``prefill_budget`` stop leaves rows mid-
            # prompt, and advancing them token-by-token here would shift
            # their remaining chunk boundaries off the unpressured
            # schedule (chunk partitioning changes reduction order, so
            # logits — and near-tie argmaxes — would drift).  Frozen rows
            # resume chunked prefill next cycle on the exact baseline
            # widths; ``run`` is data, so the trace stays at cache size 1.
            def body(_, carry):
                ms, sl = carry
                return engine_step(model, params, ms, sl,
                                   temperature=self.temperature,
                                   top_k=self.top_k, cow=cow,
                                   snap_every=snap_every)
            frozen = slots.active & ~run
            mstate, out = jax.lax.fori_loop(
                0, steps_per_sync, body,
                (mstate, slots._replace(active=slots.active & run)),
            )
            return mstate, out._replace(active=out.active | frozen)

        paged = self._paged
        snap = self._snap

        def _admit(mstate, slots, new_tokens, new_plen, new_total, new_rng,
                   mask, new_start, share_src, share_nblk):
            # release the rows' old pages, zero their recurrent state, and
            # place their decode clock at the first unshared token
            mstate = model.reset_decode_rows(mstate, mask, start=new_start)
            if paged:
                # map the donor rows' shared prompt pages (refcount bump);
                # share_nblk == 0 everywhere makes this the plain
                # admission trace — same jit cache entry either way
                from repro.serving import pager as PG

                pstate, bt = PG.share_prefix(
                    PG.PagerState(mstate["page_free"], mstate["page_top"],
                                  mstate["page_rc"]),
                    mstate["block_table"], share_src, share_nblk, mask,
                )
                mstate = {**mstate, "block_table": bt,
                          "page_free": pstate.free, "page_top": pstate.top,
                          "page_rc": pstate.rc}
            if snap:
                # recurrent families: map the donor's snapshot slots and
                # load its state at the last shared boundary, so prefill
                # resumes there with the recurrence already advanced
                mstate = model.restore_snapshots(
                    mstate, mask, share_src, share_nblk
                )
            return mstate, SlotState(
                tokens=jnp.where(mask[:, None], new_tokens, slots.tokens),
                prompt_len=jnp.where(mask, new_plen, slots.prompt_len),
                total_len=jnp.where(mask, new_total, slots.total_len),
                progress=jnp.where(mask, new_start, slots.progress),
                active=slots.active | mask,
                rng=jnp.where(mask[:, None], new_rng, slots.rng),
            )

        def _release(mstate, slots, mask):
            # harvest drain: scrub the rows' caches and release their
            # pages/slots in *every* tier (device, host, snapshots), and
            # deactivate the lanes — a cancelled or expired row may still
            # be device-active; a finished one already is not
            return model.reset_decode_rows(mstate, mask), slots._replace(
                active=slots.active & ~mask
            )

        self._step_n = jax.jit(_step_n, donate_argnums=(1, 2))
        self._admit = jax.jit(_admit, donate_argnums=(0, 1))
        self._release = jax.jit(_release, donate_argnums=(0, 1))

        if self._spillable:
            # preemption data plane: pool residency moves tiers; the row's
            # SlotState lane and live recurrent state stay put (a spilled
            # row is just an inactive lane to the decode step, which is
            # why ``decode_step`` masks recurrent-state writes by
            # ``active`` — see ``mamba_decode_block``'s ``valid``)
            def _spill(mstate, slots, mask):
                return model.spill_rows(mstate, mask), slots._replace(
                    active=slots.active & ~mask
                )

            def _restore(mstate, slots, mask):
                return model.restore_rows(mstate, mask), slots._replace(
                    active=slots.active | mask
                )

            self._spill = jax.jit(_spill, donate_argnums=(0, 1))
            self._restore = jax.jit(_restore, donate_argnums=(0, 1))
        else:
            self._spill = None
            self._restore = None

        drafter = self._drafter
        if prefill_chunk > 1:
            def _prefill_step(params, mstate, slots):
                mstate, out = engine_step(model, params, mstate, slots,
                                          temperature=self.temperature,
                                          top_k=self.top_k,
                                          chunk=prefill_chunk,
                                          cow=cow, snap_every=snap_every)
                if drafter is not None and drafter.stateful:
                    # keep the drafter's ingestion clock within one chunk
                    # of the rows it will draft for: decode-phase rows
                    # ride prefill steps at width 1 while ingestion
                    # absorbs up to ``prefill_chunk`` committed tokens,
                    # so the lag entering ``_spec_n`` is bounded by the
                    # last spec stride (<= K+1, the catch-up chunk there)
                    mstate = drafter.ingest(params, mstate, out.tokens,
                                            out.progress, prefill_chunk)
                return mstate, out
            self._prefill = jax.jit(_prefill_step, donate_argnums=(1, 2))
        else:
            self._prefill = None

        if self.spec is not None:
            spec_k = self.spec.k
            recurrent = self._recurrent

            def _spec_step(params, mstate, slots):
                bsz, buf_len = slots.tokens.shape
                act = slots.active
                prog = slots.progress
                drafts, mstate = drafter.propose(
                    params, mstate, slots.tokens, prog, act
                )
                # verify chunk: the current feed token plus the K drafts
                cur = jnp.take_along_axis(
                    slots.tokens,
                    jnp.clip(prog, 0, buf_len - 1)[:, None], axis=1,
                )
                chunk = jnp.concatenate([cur, drafts], axis=1)
                # per-row verify width: never past the row's last token,
                # never across a snapshot boundary without ending on it
                limit = jnp.full((bsz,), spec_k + 1, jnp.int32)
                if snap_every:
                    limit = jnp.minimum(
                        limit, snap_every - prog % snap_every
                    )
                w = jnp.clip(slots.total_len - 1 - prog, 1, limit)
                if recurrent:
                    # scored pass on a *discarded* state: the recurrence
                    # cannot roll back a rejected suffix, so the commit
                    # is a second, exact-width pass on the original
                    # state below (its page allocations are discarded
                    # with it — the pager arrays are functional)
                    logits, _ = model.prefill_chunk(
                        params, mstate, chunk, w, active=act,
                        cow=False, snap_every=0, logits_all=True,
                    )
                else:
                    logits, ms2 = model.prefill_chunk(
                        params, mstate, chunk, w, active=act,
                        cow=cow, snap_every=snap_every, logits_all=True,
                    )
                # greedy acceptance: keep the leading run of drafts that
                # equal the verifier's own argmax — in-chunk causality
                # makes slot j's logits exact whenever slots 0..j hold
                # true tokens, so induction gives token-identity with
                # plain greedy decode
                g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                ok = (g[:, :spec_k] == chunk[:, 1:]) & (
                    jnp.arange(spec_k, dtype=jnp.int32)[None, :]
                    < (w - 1)[:, None]
                )
                acc_n = jnp.sum(
                    jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1
                )
                stride = jnp.where(act, acc_n + 1, 0)
                if recurrent:
                    _, mstate = model.prefill_chunk(
                        params, mstate, chunk,
                        jnp.maximum(stride, 1), active=act,
                        cow=cow, snap_every=snap_every,
                    )
                else:
                    # attention caches need no second pass: garbage KV
                    # beyond the accepted frontier is never attended
                    # (causal masking by position) — rewind ``pos`` and,
                    # under the paged layout, give back the tail blocks
                    mstate = {**ms2, "pos": prog + stride}
                    if paged:
                        from repro.serving import pager as PG

                        pstate, bt = PG.release_tail(
                            PG.PagerState(
                                mstate["page_free"], mstate["page_top"],
                                mstate["page_rc"],
                            ),
                            mstate["block_table"], prog + stride, act,
                            page_size=page_size,
                        )
                        mstate = {**mstate, "block_table": bt,
                                  "page_free": pstate.free,
                                  "page_top": pstate.top,
                                  "page_rc": pstate.rc}
                # scatter the accepted tokens g[:, 0..acc_n] at
                # positions prog+1 .. prog+1+acc_n (all generated:
                # spec rows always satisfy prog >= prompt_len - 1)
                col = jax.lax.broadcasted_iota(
                    jnp.int32, (bsz, buf_len), 1
                )
                rel = col - (prog + 1)[:, None]
                sel = (act[:, None] & (rel >= 0) & (rel <= acc_n[:, None])
                       & (col >= slots.prompt_len[:, None]))
                val = jnp.take_along_axis(
                    g, jnp.clip(rel, 0, spec_k), axis=1
                )
                tokens = jnp.where(sel, val, slots.tokens)
                progress = prog + stride
                active = act & (progress < slots.total_len - 1)
                inc = jnp.stack([
                    jnp.sum(jnp.where(act, acc_n, 0)),
                    jnp.sum(jnp.where(act, w - 1, 0)),
                    jnp.sum(stride),
                ]).astype(jnp.int32)
                return mstate, SlotState(
                    tokens=tokens,
                    prompt_len=slots.prompt_len,
                    total_len=slots.total_len,
                    progress=progress,
                    active=active,
                    rng=slots.rng,
                ), inc

            def _spec_n(params, mstate, slots, acc, run):
                # same freeze contract as ``_step_n`` (budget-stopped
                # rows keep their chunk boundaries); ``acc`` is the
                # cumulative device counter [accepted, proposed, emitted]
                frozen = slots.active & ~run

                def body(_, carry):
                    ms, sl, ac = carry
                    ms, sl, inc = _spec_step(params, ms, sl)
                    return ms, sl, ac + inc

                mstate, out, acc = jax.lax.fori_loop(
                    0, steps_per_sync, body,
                    (mstate, slots._replace(active=slots.active & run),
                     acc),
                )
                return (mstate, out._replace(active=out.active | frozen),
                        acc)

            self._spec_n = jax.jit(_spec_n, donate_argnums=(1, 2, 3))
            self._acc = jnp.zeros((3,), jnp.int32)
        else:
            self._spec_n = None
            self._acc = None

    # -- request intake ------------------------------------------------------

    def submit(self, tokens, max_new_tokens: Optional[int] = None, *,
               priority: int = 0,
               deadline_ms: Optional[float] = None) -> "RequestHandle":
        """Queue a request; returns a :class:`RequestHandle` (an ``int``
        subclass — the request id everywhere an id is expected, plus
        ``.cancel()``).

        Accepts either the positional form (``submit(tokens,
        max_new_tokens, ...)``) or a prebuilt spec: ``submit(
        Request.spec(tokens, max_new_tokens, priority=..., ...))``.
        ``priority`` (larger = more important) and ``deadline_ms`` (SLO
        budget from now, against the monotonic clock; None = none) feed
        the scheduler contract in the class docstring.  Rejections —
        over-length, empty, pool-impossible, queue-full — always name
        the request id they rejected."""
        if isinstance(tokens, Request):
            req = tokens
            if max_new_tokens is not None:
                raise TypeError(
                    "submit(Request, ...) takes the whole spec from the "
                    "Request — max_new_tokens must not also be passed"
                )
            tokens = req.tokens
            max_new_tokens = req.max_new_tokens
            priority = req.priority
            deadline_ms = req.deadline_ms
        elif max_new_tokens is None:
            raise TypeError(
                "submit() needs max_new_tokens unless a Request spec "
                "is passed"
            )
        if self._paged:
            need = self._pages_needed(len(tokens) + max_new_tokens)
            if need > self.n_pages:
                # reject now: the queue would otherwise starve behind a
                # request that can never reserve enough pages
                rid = self.queue.peek_next_id()
                raise ValueError(
                    f"request {rid}: needs {need} pages > pool size "
                    f"{self.n_pages} (prompt {len(tokens)} + "
                    f"{max_new_tokens} new, page_size {self.page_size})"
                )
        rid = self.queue.submit(tokens, max_new_tokens, priority=priority,
                                deadline_ms=deadline_ms)
        now = time.perf_counter()
        self._t_submit[rid] = now
        if deadline_ms is not None:
            self._deadline[rid] = now + deadline_ms / 1e3
        return RequestHandle(rid, self)

    def cancel(self, req_id: int) -> bool:
        """Cancel a request wherever it lives.  Still-queued: removed
        immediately.  Resident — device-active, mid-prefill, or spilled
        to the host tier: marked, then drained through the jitted release
        path at the next harvest (pages and snapshot slots return to
        their pools in every tier; no output is recorded).  Returns False
        when the id is unknown or already finished."""
        req = self.queue.cancel(req_id)
        if req is not None:
            self.cancelled.add(req_id)
            self._deadline.pop(req_id, None)
            self._t_submit.pop(req_id, None)
            return True
        for r in self._slot_req:
            if r is not None and r.req_id == req_id:
                self._cancel_req.add(req_id)
                return True
        return False

    def set_fault_plan(self, plan: Optional[FaultPlan]) -> None:
        """Arm a plan with the harvest-cycle clock rewound to 0 (benchmark
        drivers arm after compile warm-up so event cycles land on a
        schedule-stable clock)."""
        self.fault_plan = plan
        self._cycle = 0

    def _apply_faults(self) -> None:
        """Fire the armed plan's events for the current harvest cycle.
        Every injection flows through the normal scheduler paths
        (``repro.serving.faults`` documents the kinds)."""
        if self.fault_plan is None:
            return
        for e in self.fault_plan.at(self._cycle):
            if e.kind == "exhaust_pool":
                self._fault_hold_pages = min(e.pages, self.n_pages)
            elif e.kind == "release_pool":
                self._fault_hold_pages = 0
            elif e.kind == "cancel":
                self.cancel(e.req_id)
            elif e.kind == "deadline":
                self._deadline[e.req_id] = (
                    time.perf_counter() + e.deadline_ms / 1e3
                )
            elif e.kind == "poison":
                self._poison_req.add(e.req_id)

    def _effective_pages(self) -> int:
        """Pool size the reservation ledger admits against — shrunk while
        an ``exhaust_pool`` fault holds pages hostage."""
        return self.n_pages - self._fault_hold_pages

    def _req_key(self, req_id: int) -> np.ndarray:
        """Per-request sampling key, a pure function of (engine seed,
        req_id) — see the ``_seed`` comment in ``__init__``."""
        gen = np.random.Generator(
            np.random.Philox(np.random.SeedSequence((self._seed, req_id)))
        )
        return gen.integers(0, 2 ** 32, size=2, dtype=np.uint32)

    def _pages_needed(self, total_len: int) -> int:
        from repro.serving.pager import pages_needed
        return pages_needed(total_len, self.page_size)

    # -- host-side prefix index (no device sync anywhere) --------------------

    def _prefix_chain(self, tokens: np.ndarray):
        """Chained hashes of the page-aligned full prompt chunks: chunk i's
        hash folds in chunk i-1's, so a hit at depth i certifies the whole
        prefix — the page's K/V depends on everything before it, not just
        its own tokens."""
        s = self.page_size
        h = 0x51ED2701
        for i in range(len(tokens) // s):
            h = hash((h, tokens[i * s:(i + 1) * s].tobytes()))
            yield i, h

    def _register_prefix(self, b: int, tokens: np.ndarray) -> None:
        ep = self._slot_epoch[b]
        for _, h in self._prefix_chain(tokens):
            ent = self._prefix_index.get(h)
            if ent is not None:
                src, src_ep = ent
                if (src_ep == self._slot_epoch[src]
                        and self._slot_req[src] is not None
                        and not self._row_spilled[src]):
                    # a live row already serves this chunk: keep it (a
                    # sharer overwriting its donor would take the entry
                    # to its own — likely earlier — grave, leaving the
                    # still-resident donor unmatchable)
                    continue
            self._prefix_index[h] = (b, ep)
            self._slot_hashes[b].append(h)

    def _evict_prefix(self, b: int) -> None:
        """Invalidate slot b's index entries (request released).  The epoch
        bump is what guarantees staleness; the deletes just keep the index
        bounded by resident prompts."""
        old = (b, self._slot_epoch[b])
        self._slot_epoch[b] += 1
        dropped = False
        for h in self._slot_hashes[b]:
            if self._prefix_index.get(h) == old:
                del self._prefix_index[h]
                dropped = True
        self._slot_hashes[b] = []
        if dropped:
            # hand the dropped chunks to surviving holders: a sharer keeps
            # the donor's pages resident (refcounts), so it can donate them
            # onward — without this, a shared prefix would go unmatchable
            # the moment its original donor finishes, even though the
            # pages live on (re-registration only fills gaps; entries that
            # still point at live rows are kept).  Spilled rows don't
            # donate — their pages are on the host tier.
            for s, req in enumerate(self._slot_req):
                if req is not None and not self._row_spilled[s]:
                    self._register_prefix(s, req.tokens)

    def _match_prefix(self, tokens: np.ndarray):
        """Longest page-aligned shared prefix among resident rows: returns
        (donor slot, shared block count), (0, 0) when nothing matches.

        A hit is honored only if the donor still holds its request (epoch
        check), its host-mirror progress shows the chunk fully *written*
        (mapped pages alone could still be mid-prefill), the chunk is all
        prompt (never a donor's generated tokens), and the tokens compare
        equal — the hash only routes, equality decides.

        The same progress check certifies *snapshot* availability for the
        recurrent families: snapshot engines clip chunk widths so every
        boundary a row passes is a step endpoint (captured), and shared
        slots travel with their boundaries, so boundary ``k`` has a
        snapshot exactly when the donor's progress has reached ``k *
        page_size`` — the index records availability without any extra
        bookkeeping."""
        if not self._share_eligible:
            return 0, 0
        best = (0, 0)
        s = self.page_size
        for i, h in self._prefix_chain(tokens):
            ent = self._prefix_index.get(h)
            if ent is None:
                continue
            src, ep = ent
            end = (i + 1) * s
            req = self._slot_req[src]
            if (ep != self._slot_epoch[src] or req is None
                    or self._row_spilled[src]
                    or req.prompt_len < end
                    or self._row_progress[src] < end
                    or not np.array_equal(tokens[:end], req.tokens[:end])):
                continue
            best = (src, i + 1)
        return best

    def _plan_admission(self, req: Request):
        """Host-side admission plan for one request: prefix match, shared
        depth, CoW spare, worst-case page need — pure mirror arithmetic,
        re-runnable after a preemption changes the donor set (the match,
        and so the need, can only shrink)."""
        src, nblk = self._match_prefix(req.tokens)
        if self._recurrent:
            # recurrent families resume *from a restored snapshot*, so
            # the resume point must be a boundary strictly inside the
            # prompt (the re-fed last token then always lands in an
            # unshared page — recurrent sharing never CoWs)
            nblk = min(nblk, (req.prompt_len - 1) // self.page_size)
        shared = nblk * self.page_size
        # always re-feed at least the last prompt token: its logits
        # seed generation (a fully shared attention prompt re-feeds
        # exactly one token, whose write CoWs the final shared page)
        start = min(shared, req.prompt_len - 1)
        cow = 1 if shared > start else 0
        if self._paged:
            need = self._pages_needed(req.total_len) + cow
            if need > self.n_pages:
                # the CoW spare would overflow the pool: serve unshared
                src = nblk = start = cow = 0
                need = self._pages_needed(req.total_len)
        else:
            need = 0
        return src, nblk, start, cow, need

    def _expire_queued(self, now: float) -> None:
        """Deadline sweep over still-queued requests (resident rows expire
        at harvest, where the device sync already happened)."""
        if not self._deadline:
            return
        for rid in self.queue.pending_ids():
            t = self._deadline.get(rid)
            if t is not None and now >= t:
                if self.queue.cancel(rid) is not None:
                    self.expired.add(rid)
                    self._deadline.pop(rid, None)
                    self._t_submit.pop(rid, None)

    def _try_preempt(self, req: Request, need: int, protected) -> bool:
        """Spill strictly-lower-priority victims until ``req``'s
        reservation fits; commit only if the chosen set actually admits
        it (no thrashing spills).  Victim order: lowest priority first,
        then least progress (least work lost), then oldest.
        ``protected`` rows (this refill's pending admissions and their
        prefix donors) are never victims — spilling a pending donor
        would tear pages out from under the _admit mapping below."""
        if self._spill is None:
            return False
        victims = [
            b for b, r in enumerate(self._slot_req)
            if r is not None and not self._row_spilled[b]
            and r.priority < req.priority
            and b not in protected
            and r.req_id not in self._cancel_req
            and r.req_id not in self._poison_req
        ]
        victims.sort(key=lambda b: (self._slot_req[b].priority,
                                    self._row_progress[b],
                                    self._slot_req[b].req_id))
        chosen = []
        freed = 0
        for b in victims:
            if (self._pages_reserved - freed + need
                    <= self._effective_pages()):
                break
            chosen.append(b)
            freed += self._row_pages[b]
        if (not chosen
                or self._pages_reserved - freed + need
                > self._effective_pages()):
            return False
        mask = np.zeros((self.batch,), bool)
        for b in chosen:
            mask[b] = True
            self._spill_need[b] = self._row_pages[b]
            self._pages_reserved -= self._row_pages[b]
            self._row_pages[b] = 0
            self._row_spilled[b] = True
            self.preemptions += 1
            # a spilled row's pages leave the device: it stops donating
            # (sharers keep already-mapped pages alive via refcounts;
            # only *new* matches are ruled out)
            self._evict_prefix(b)
        self._mstate, self._slots = self._spill(
            self._mstate, self._slots, jnp.asarray(mask)
        )
        return True

    def _try_restore(self, now: float) -> int:
        """Bring spilled rows back while their worst-case reservation fits
        (the reservation gate is exactly what guarantees the jitted
        restore's device-side pops never find the free list dry).
        Highest priority first, then oldest; a spilled row defers to a
        strictly-higher-priority queue head that could itself fit, and
        doomed rows (pending cancel/poison, past deadline) stay spilled
        — the harvest drains their host-tier slots directly."""
        if self._restore is None:
            return 0
        spilled = [b for b in range(self.batch) if self._row_spilled[b]]
        if not spilled:
            return 0
        head = self.queue.peek()
        head_fits = (
            head is not None
            and self._pages_needed(head.total_len)
            <= self._effective_pages()
        )
        spilled.sort(key=lambda b: (-self._slot_req[b].priority,
                                    self._slot_req[b].req_id))
        mask = np.zeros((self.batch,), bool)
        n = 0
        for b in spilled:
            req = self._slot_req[b]
            rid = req.req_id
            if rid in self._cancel_req or rid in self._poison_req:
                continue
            t = self._deadline.get(rid)
            if t is not None and now >= t:
                continue
            if head_fits and head.priority > req.priority:
                continue
            need = self._spill_need[b]
            if self._pages_reserved + need > self._effective_pages():
                continue
            mask[b] = True
            self._row_spilled[b] = False
            self._row_pages[b] = need
            self._spill_need[b] = 0
            self._pages_reserved += need
            self.restores += 1
            n += 1
        if n == 0:
            return 0
        self._mstate, self._slots = self._restore(
            self._mstate, self._slots, jnp.asarray(mask)
        )
        if self._share_eligible:
            # device-resident again: the row may donate its prefix anew
            for b in spilled:
                if mask[b]:
                    self._register_prefix(b, self._slot_req[b].tokens)
        return n

    def _refill(self) -> List[int]:
        """Admit queued requests into free rows (one jitted masked write);
        returns the admitted request ids.

        Scheduler order per cycle: expire queued deadlines, restore
        spilled rows that fit, then admit the queue head while a free
        row and (paged layout) a worst-case page reservation exist —
        preempting strictly-lower-priority victims when the head cannot
        reserve (class docstring has the full contract).  Admission
        stops at the first unadmittable head — no reordering past a
        starving request beyond what the priority queue itself encodes.

        Prefix sharing: each admitted prompt is matched against the
        host-side index; on a hit the donor's leading blocks are mapped
        (``share_prefix`` inside ``_admit``) and the row starts at the
        first unshared token.  Reservation stays the *full* worst case
        plus one CoW spare for a fully shared prompt — a donor may finish
        first and leave the sharer sole holder of the shared pages, so
        subtracting them would let the pool over-commit (see class
        docstring); the sharing win is resident bytes and TTFT, not
        admission capacity.
        """
        now = time.perf_counter()
        self._expire_queued(now)
        self._try_restore(now)
        free = [b for b, r in enumerate(self._slot_req) if r is None]
        if not free or not self.queue:
            return []
        new_tokens = np.zeros((self.batch, self.max_len), np.int32)
        new_plen = np.ones((self.batch,), np.int32)
        new_total = np.ones((self.batch,), np.int32)
        new_rng = np.zeros((self.batch, 2), np.uint32)
        mask = np.zeros((self.batch,), bool)
        new_start = np.zeros((self.batch,), np.int32)
        share_src = np.zeros((self.batch,), np.int32)
        share_nblk = np.zeros((self.batch,), np.int32)
        registrations = []
        pending: Set[int] = set()   # rows admitted in this refill
        donors: Set[int] = set()    # their prefix donors
        admitted: List[int] = []
        for b in free:
            req = self.queue.peek()
            if req is None:
                break
            src, nblk, start, cow, need = self._plan_admission(req)
            if (self._paged
                    and self._pages_reserved + need
                    > self._effective_pages()):
                if not self._try_preempt(req, need, pending | donors):
                    break
                # victims left the donor set: re-plan (the match can only
                # shrink, so the committed preemption still fits)
                src, nblk, start, cow, need = self._plan_admission(req)
            self.queue.pop()
            self._slot_req[b] = req
            self._row_progress[b] = start
            self._row_pages[b] = need
            self._pages_reserved += need
            new_tokens[b, : req.prompt_len] = req.tokens
            new_plen[b] = req.prompt_len
            new_total[b] = req.total_len
            new_rng[b] = self._req_key(req.req_id)
            mask[b] = True
            new_start[b] = start
            share_src[b] = src
            share_nblk[b] = nblk
            self.shared_prompt_tokens += start
            self.cow_pages += cow
            if self._share_eligible:
                registrations.append((b, req.tokens))
            pending.add(b)
            if nblk > 0:
                donors.add(src)
            admitted.append(req.req_id)
        if not admitted:
            return []
        self._mstate, self._slots = self._admit(
            self._mstate, self._slots,
            jnp.asarray(new_tokens), jnp.asarray(new_plen),
            jnp.asarray(new_total), jnp.asarray(new_rng),
            jnp.asarray(mask), jnp.asarray(new_start),
            jnp.asarray(share_src), jnp.asarray(share_nblk),
        )
        # register *after* the device mapping exists: rows admitted in this
        # same batch must not pick each other as donors (their shared
        # blocks only materialize in the _admit call above)
        for b, toks in registrations:
            self._register_prefix(b, toks)
        return admitted

    # -- serving loop --------------------------------------------------------

    def _advance_mirror(self, widths: List[int]):
        """Replay one device step's progress update on the host mirror.

        ``widths[b]`` is the stride row b advanced (a chunk width for a
        prefill step, ``steps_per_sync`` for a fused decode call — the
        decode case over-counts past done-detection, which the
        ``total_len - 1`` clamp absorbs exactly like the device's
        ``active`` mask).  Accumulates prompt-ingestion counts and returns
        ``(crossed, advanced)``: the req_ids whose first generated token
        was produced by this step (TTFT is stamped by the caller at the
        next device sync, when that token actually exists), and the
        positions the live rows advanced in all.
        """
        crossed: List[int] = []
        advanced = 0
        for b, req in enumerate(self._slot_req):
            if req is None or self._row_spilled[b]:
                # a spilled row's lane is device-inactive: no progress
                continue
            p = self._row_progress[b]
            if p >= req.total_len - 1:
                continue
            np_ = min(p + widths[b], req.total_len - 1)
            self.prompt_tokens += (
                min(np_, req.prompt_len) - min(p, req.prompt_len)
            )
            if p < req.prompt_len <= np_:
                crossed.append(req.req_id)
            self._row_progress[b] = np_
            advanced += np_ - p
        return crossed, advanced

    def _chunk_limit(self, progress: int) -> int:
        """Host mirror of ``engine_step``'s chunk-width cap: snapshot
        engines clip chunks to end at page boundaries so every boundary
        state is captured (the two formulas must stay identical — the
        mirror's TTFT/ingestion ledger depends on it)."""
        if self._snap_every:
            return min(self.prefill_chunk,
                       self._snap_every - progress % self._snap_every)
        return self.prefill_chunk

    def _prompt_phase_rows(self) -> bool:
        """True while some occupied, unfinished row still has >= 2 prompt
        tokens to feed — the regime where a chunked step beats a decode
        step (a single remaining prompt token is just a decode feed)."""
        return any(
            req is not None
            and not self._row_spilled[b]
            and self._row_progress[b] < req.total_len - 1
            and req.prompt_len - self._row_progress[b] >= 2
            for b, req in enumerate(self._slot_req)
        )

    def step(self) -> int:
        """One sync cycle: apply scripted faults, refill (deadline sweep,
        restores, admission with preemption), chunked prefill until no
        row is mid-prompt (bounded by ``prefill_budget`` when set),
        ``steps_per_sync`` fused decode steps, then a single host
        readback to harvest finished — and drain cancelled / expired /
        poisoned — rows.  Returns the number of requests completed this
        cycle.

        Each part runs under its profiler span (``SPANS``), so a device
        trace can put every idle gap down to the host's part of a cycle.
        """
        annotate = jax.profiler.TraceAnnotation
        with annotate("engine.step", cycle=self._cycle + 1):
            self._apply_faults()
            self._cycle += 1
            with annotate("engine.refill") as span:
                admitted = self._refill()
                if admitted and annotate.is_enabled():
                    span.set_metadata(
                        admitted=" ".join(map(str, admitted)))
            if not any(r is not None for r in self._slot_req):
                return 0
            crossed: List[int] = []
            if self._prefill is not None:
                # prompt ingestion: chunked steps, back-to-back dispatches,
                # no host sync — the mirror knows each row's width without
                # one.  Decode-phase rows ride along one token per chunk
                # step.  ``prefill_budget`` caps the chunk steps per cycle
                # so a long prompt cannot starve resident decodes of a
                # whole harvest interval; leftover prompt tokens continue
                # next cycle or token-by-token inside the fused decode call
                # below.
                nchunks = 0
                while self._prompt_phase_rows() and (
                        not self.prefill_budget
                        or nchunks < self.prefill_budget):
                    with annotate("engine.prefill"):
                        widths = [
                            max(1, min(
                                self._chunk_limit(self._row_progress[b]),
                                req.prompt_len - self._row_progress[b]))
                            if req is not None else 1
                            for b, req in enumerate(self._slot_req)
                        ]
                        self._mstate, self._slots = self._prefill(
                            self.params, self._mstate, self._slots
                        )
                        self.prefill_steps += 1
                        nchunks += 1
                        new, advanced = self._advance_mirror(widths)
                        crossed += new
                        self.prefill_slots += self.batch * self.prefill_chunk
                        self.prefill_positions += advanced
            with annotate("engine.decode"):
                # rows a budget stop left mid-prompt are frozen for the
                # fused decode call (see ``_step_n``): advancing them
                # token-by-token would change their chunk partitioning,
                # and with it the logits
                run = np.ones((self.batch,), bool)
                if self._prefill is not None:
                    for b, req in enumerate(self._slot_req):
                        if (req is not None and not self._row_spilled[b]
                                and req.prompt_len - self._row_progress[b]
                                >= 2):
                            run[b] = False
                self.steps += self.steps_per_sync
                self.decode_row_steps += self.batch * self.steps_per_sync
                if self._spec_n is not None:
                    # draft-and-verify decode: per-row strides are
                    # data-dependent (accepted lengths), so the host mirror
                    # is refreshed from the harvest readback below instead
                    # of replayed arithmetically
                    self._mstate, self._slots, self._acc = self._spec_n(
                        self.params, self._mstate, self._slots, self._acc,
                        jnp.asarray(run),
                    )
                else:
                    self._mstate, self._slots = self._step_n(
                        self.params, self._mstate, self._slots,
                        jnp.asarray(run)
                    )
                    new, advanced = self._advance_mirror(
                        [self.steps_per_sync if run[b] else 0
                         for b in range(self.batch)]
                    )
                    crossed += new
                    self.decode_positions += advanced
            # the one host sync of the cycle (allocator tops — and, under
            # speculation, per-row progress and the accept counter — ride
            # along; no extra round-trips)
            fetch = [self._slots.active, self._slots.tokens]
            i_prog = i_acc = i_page = i_snap = -1
            if self._spec_n is not None:
                fetch.append(self._slots.progress)
                i_prog = len(fetch) - 1
                fetch.append(self._acc)
                i_acc = len(fetch) - 1
            if self._paged:
                fetch.append(self._mstate["page_top"])
                i_page = len(fetch) - 1
            if self._snap:
                fetch.append(self._mstate["snap_top"])
                i_snap = len(fetch) - 1
            with annotate("engine.harvest"):
                got = list(jax.device_get(tuple(fetch)))
            active, tokens = got[0], got[1]
            if self._spec_n is not None:
                # mirror refresh: the device's per-row progress is the
                # truth under speculation; the same deltas the
                # deterministic replay would have produced (ingestion
                # counts, TTFT crossings, positions advanced) are recovered
                # from old-vs-new
                devprog = got[i_prog]
                for b, req in enumerate(self._slot_req):
                    if req is None:
                        continue
                    old = self._row_progress[b]
                    dev = int(devprog[b])
                    plen = req.prompt_len
                    self.prompt_tokens += min(dev, plen) - min(old, plen)
                    if old < plen <= dev:
                        crossed.append(req.req_id)
                    self._row_progress[b] = dev
                    self.decode_positions += dev - old
                acc = got[i_acc]
                self.spec_accepted = int(acc[0])
                self.spec_proposed = int(acc[1])
                self.spec_emitted = int(acc[2])
            if self._paged:
                self.peak_pages_in_use = max(
                    self.peak_pages_in_use, self.n_pages - int(got[i_page])
                )
            if self._snap:
                self.peak_snaps_in_use = max(
                    self.peak_snaps_in_use,
                    self.n_snap_slots - int(got[i_snap])
                )
            # the readback above materialized every token this cycle
            # produced, so first-token latencies are stamped here, not at
            # dispatch (the pop keeps the submit-time ledger bounded by
            # pending requests)
            now = time.perf_counter()
            for rid in crossed:
                t0 = self._t_submit.pop(rid, None)
                if t0 is not None:
                    self.ttft.setdefault(rid, now - t0)
            finished = 0
            release = np.zeros((self.batch,), bool)
            drained = False
            for b, req in enumerate(self._slot_req):
                if req is None:
                    continue
                rid = req.req_id
                t = self._deadline.get(rid)
                if rid in self._cancel_req:
                    self.cancelled.add(rid)
                elif rid in self._poison_req:
                    self.poisoned.add(rid)
                elif t is not None and now >= t:
                    self.expired.add(rid)
                elif self._row_spilled[b] or active[b]:
                    continue    # still running (or parked on the host tier)
                else:
                    # finished for real: the generated span is the payload
                    out = tokens[b, req.prompt_len : req.total_len].copy()
                    self.outputs[rid] = out
                    self.generated += out.size
                    self._drop_row(b)
                    release[b] = True
                    finished += 1
                    continue
                # cancelled / poisoned / past-deadline: no payload; the row
                # — device-active, mid-prefill, or spilled — drains through
                # the same release path, surrendering pages and snapshot
                # slots in every tier
                self._drop_row(b)
                release[b] = True
                drained = True
            if np.any(release) and (self._paged or self._snap or drained):
                # free-on-completion: the rows' pages and snapshot slots
                # (device *and* host tiers) return to their pools now, not
                # when the slot happens to be refilled; drained rows
                # additionally need their lanes deactivated (a cancelled
                # row may still be device-active)
                with annotate("engine.release"):
                    self._mstate, self._slots = self._release(
                        self._mstate, self._slots, jnp.asarray(release)
                    )
            return finished

    def _drop_row(self, b: int) -> None:
        """Host-mirror bookkeeping for a row leaving the batch (finished
        or drained): reservation, spill mirrors, prefix entries, SLO
        ledgers."""
        rid = self._slot_req[b].req_id
        self._slot_req[b] = None
        self._pages_reserved -= self._row_pages[b]
        self._row_pages[b] = 0
        self._spill_need[b] = 0
        self._row_spilled[b] = False
        # the slot's prompt leaves the prefix index; its *pages* live
        # on while any sharer still references them (device refcounts)
        self._evict_prefix(b)
        self._cancel_req.discard(rid)
        self._poison_req.discard(rid)
        self._deadline.pop(rid, None)
        self._t_submit.pop(rid, None)

    def run(self) -> Dict[int, np.ndarray]:
        """Serve until queue and slots drain; returns {req_id: generated}."""
        while self.queue or any(r is not None for r in self._slot_req):
            self.step()
        return self.outputs

    def reset_stats(self) -> None:
        """Zero every accumulated statistic (post-warm-up, pre-measurement).

        Lives next to the counters it owns so benchmark drivers don't
        hand-mirror the list; serving state (slots, caches, queue) is
        untouched."""
        self.outputs.clear()
        self.ttft.clear()
        self.steps = self.prefill_steps = 0
        self.generated = self.prompt_tokens = 0
        self.prefill_slots = self.prefill_positions = 0
        self.decode_row_steps = self.decode_positions = 0
        self.spec_proposed = self.spec_accepted = self.spec_emitted = 0
        if self._acc is not None:
            self._acc = jnp.zeros((3,), jnp.int32)
        self.peak_pages_in_use = self.peak_snaps_in_use = 0
        self.shared_prompt_tokens = self.cow_pages = 0
        self.preemptions = self.restores = 0
        self.cancelled.clear()
        self.expired.clear()
        self.poisoned.clear()

    def kv_bytes_per_page(self) -> int:
        """Bytes one page occupies across all layer slabs (K and V) —
        shape arithmetic frozen at construction, no pytree walk."""
        return self._kv_bytes_per_page

    def kv_resident_bytes(self, *, peak: bool = False) -> int:
        """Resident KV-cache footprint: allocated bytes under the paged
        layout (current or peak), the full slab under contiguous.  Byte
        factors are cached at construction; only the *current* paged
        residency reads a device scalar (``page_top``)."""
        if self._paged:
            pages = (
                self.peak_pages_in_use if peak
                else self.n_pages - int(self._mstate["page_top"])
            )
            return pages * self._kv_bytes_per_page
        return self._contig_kv_bytes

    def stats(self) -> Dict[str, float]:
        out = {
            "decode_steps": float(self.steps),
            "prefill_steps": float(self.prefill_steps),
            "generated_tokens": float(self.generated),
            "prompt_tokens": float(self.prompt_tokens),
            "batch": float(self.batch),
            "prefill_slots": float(self.prefill_slots),
            "prefill_positions": float(self.prefill_positions),
            "decode_row_steps": float(self.decode_row_steps),
            "decode_positions": float(self.decode_positions),
        }
        if self._paged:
            out["kv_pages"] = float(self.n_pages)
            out["kv_pages_peak"] = float(self.peak_pages_in_use)
            out["kv_resident_bytes_peak"] = float(
                self.kv_resident_bytes(peak=True)
            )
        if self._recurrent:
            # device bytes of the live SSM and conv state of every row
            out["recurrent_state_bytes"] = float(self._recurrent_bytes)
        if self._snap:
            out["snap_slots"] = float(self.n_snap_slots)
            out["snap_slots_peak"] = float(self.peak_snaps_in_use)
        if self.prefix_sharing:
            out["shared_prompt_tokens"] = float(self.shared_prompt_tokens)
            out["cow_pages"] = float(self.cow_pages)
        if self._spillable:
            out["preemptions"] = float(self.preemptions)
            out["restores"] = float(self.restores)
        if self.spec is not None:
            out["spec_proposed"] = float(self.spec_proposed)
            out["spec_accepted"] = float(self.spec_accepted)
            out["spec_emitted"] = float(self.spec_emitted)
            out["spec_accept_rate"] = (
                self.spec_accepted / max(self.spec_proposed, 1)
            )
        out["cancelled"] = float(len(self.cancelled))
        out["expired"] = float(len(self.expired))
        return out


def serve_all(
    model: Model,
    params,
    requests,
    *,
    batch: int,
    max_len: int,
    steps_per_sync: int = 8,
    cache: Optional[CacheConfig] = None,
    config: Optional[EngineConfig] = None,
    **engine_kwargs,
) -> Dict[int, np.ndarray]:
    """Convenience: submit ``[(tokens, max_new_tokens), ...]`` and drain.

    Accepts the typed config objects (``cache=`` / ``config=`` — the
    preferred surface; ``steps_per_sync`` then lives in ``config``) or
    the legacy kwarg pile, which flows through the engine's deprecation
    adapter.  Returns outputs keyed by submission order (0..n-1)."""
    if cache is not None or config is not None:
        eng = ServingEngine(
            model, params, batch=batch, max_len=max_len,
            cache=cache, config=config, **engine_kwargs,
        )
    elif engine_kwargs:
        # legacy kwarg pile: flows through the engine's from_kwargs
        # adapter (DeprecationWarning attributed to this call's caller)
        eng = ServingEngine(
            model, params, batch=batch, max_len=max_len,
            steps_per_sync=steps_per_sync, **engine_kwargs,
        )
    else:
        eng = ServingEngine(
            model, params, batch=batch, max_len=max_len,
            config=EngineConfig(steps_per_sync=steps_per_sync),
        )
    for tokens, gen in requests:
        eng.submit(tokens, gen)
    return eng.run()
