"""Chunked-prefill tests — multi-token prompt ingestion must be
token-identical to token-by-token ingestion through the whole decode stack
(kernel, ops dispatch, pager, model, engine), across KV layouts and
backends, including chunk widths that don't divide the prompt length and
requests admitted mid-stream into a busy batch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import use_backend
from repro.configs.registry import get_arch
from repro.kernels.flash_attention import (
    flash_prefill_chunk_paged_pallas,
    flash_prefill_chunk_pallas,
)
from repro.kernels.ops import (
    _attention_prefill_chunk_paged_ref,
    _attention_prefill_chunk_ref,
)
from repro.models.model import build_model
from repro.serving import ServingEngine
from repro.serving.pager import (
    PagerState,
    alloc_range,
    init_block_table,
    init_pager,
    write_page_chunk,
)

BACKENDS = ["reference", "pallas"]
# one dense, one moe, one hybrid, one pure ssm: the chunk path must cover
# chunked attention, chunked MoE dispatch, and the chunked SSD scan with
# carried recurrent state (decode is its C=1 case — same accumulation
# order, so chunked and token-by-token ingestion stay token-identical)
CHUNK_ARCHS = ["qwen2.5-3b", "qwen3-moe-235b-a22b", "zamba2-2.7b",
               "mamba2-2.7b"]


def _cfg(arch):
    cfg = get_arch(arch).reduced()
    cfg = dataclasses.replace(cfg, dtype="float32")
    if cfg.n_experts:
        # chunked steps route B*C tokens where decode routes B; only the
        # no-drop regime is batch-composition-independent (engine docstring)
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    return cfg


def _model_params(arch):
    cfg = _cfg(arch)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _serve(model, params, reqs, **kw):
    eng = ServingEngine(model, params, batch=2, max_len=16,
                        steps_per_sync=3, **kw)
    rids = [eng.submit(t, g) for t, g in reqs]
    got = eng.run()
    return eng, [got[r].tolist() for r in rids]


# -- kernel <-> oracle lock-step --------------------------------------------

@pytest.mark.parametrize("window", [None, 6])
def test_prefill_chunk_kernel_matches_oracle(window):
    """The Pallas chunk kernels and the jnp oracles must agree on both
    layouts, including per-row starts/widths (padding rows) and windows."""
    b, c, hq, hkv, d, smax = 3, 5, 4, 2, 8, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, c, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, smax, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, smax, hkv, d), jnp.float32)
    start = jnp.asarray([0, 7, 20], jnp.int32)
    width = jnp.asarray([5, 3, 1], jnp.int32)
    want = _attention_prefill_chunk_ref(q, k, v, start, width, window=window)
    got = flash_prefill_chunk_pallas(q, k, v, start, width, window=window,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    # paged: same math through a block table over a shared pool
    page, n_pages, maxb = 4, 12, 8
    kp = jax.random.normal(ks[1], (n_pages, page, hkv, d), jnp.float32)
    vp = jax.random.normal(ks[3], (n_pages, page, hkv, d), jnp.float32)
    bt = np.full((b, maxb), -1, np.int32)
    bt[0, :2] = [0, 1]
    bt[1, :3] = [2, 3, 4]
    bt[2, :6] = [5, 6, 7, 8, 9, 10]
    bt = jnp.asarray(bt)
    want = _attention_prefill_chunk_paged_ref(q, kp, vp, start, width, bt,
                                              window=window)
    got = flash_prefill_chunk_paged_pallas(q, kp, vp, start, width, bt,
                                           window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_prefill_chunk_kernel_tiled_and_padded():
    """Force small KV tiles (bk=8) on a non-multiple cache length so the
    kernel walks several tiles and a padded tail — padded keys must stay
    masked for every chunk row."""
    from repro.core.registry import clear_tuning, set_tuning

    b, c, hq, hkv, d, smax = 2, 4, 4, 2, 8, 27
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, c, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, smax, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, smax, hkv, d), jnp.float32)
    start = jnp.asarray([23, 10], jnp.int32)
    width = jnp.asarray([4, 2], jnp.int32)
    want = _attention_prefill_chunk_ref(q, k, v, start, width)
    set_tuning("flash_prefill", bk=8)
    try:
        got = flash_prefill_chunk_pallas(q, k, v, start, width,
                                         interpret=True)
    finally:
        clear_tuning()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- pager: multi-page-per-step allocation ----------------------------------

def test_alloc_range_maps_exact_blocks_and_conserves():
    """alloc_range must map exactly the blocks covering start..end (per-row
    widths), keep the free-list/block-table partition intact, and compose
    with write_page_chunk so padding positions never touch the pool."""
    page_size, n_pages, b, maxb = 4, 16, 3, 6
    pager = init_pager(n_pages)
    bt = init_block_table(b, maxb)
    start = jnp.asarray([0, 6, 21], jnp.int32)
    width = jnp.asarray([7, 1, 3], jnp.int32)   # rows straddle 2 / 1 / 1 blk
    pager, bt = alloc_range(pager, bt, start, start + width - 1,
                            page_size=page_size, max_chunk=8)
    bt_np = np.asarray(bt)
    mapped = [sorted(np.nonzero(r >= 0)[0].tolist()) for r in bt_np]
    assert mapped == [[0, 1], [1], [5]]
    n_mapped = int((bt_np >= 0).sum())
    assert int(pager.top) == n_pages - n_mapped
    # partition: free prefix + mapped pages == all pages, no duplicates
    owned = sorted(
        np.asarray(pager.free)[: int(pager.top)].tolist()
        + bt_np[bt_np >= 0].tolist()
    )
    assert owned == list(range(n_pages))
    # chunk write: padding (i >= width) and unmapped blocks must drop
    pool = jnp.zeros((n_pages, page_size, 1, 2), jnp.float32)
    new = jnp.ones((b, 8, 1, 2), jnp.float32)
    pool = write_page_chunk(pool, new, bt, start, width)
    written = int((np.asarray(pool) != 0).sum() // 2)
    assert written == int(width.sum())


# -- engine: chunked == token-by-token --------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("arch", CHUNK_ARCHS)
def test_chunked_prefill_matches_token_by_token(arch, backend):
    """prefill_chunk=4 over prompts of length 3..9 (widths that don't
    divide the chunk), 5 requests through 2 slots (mid-stream admissions
    at heterogeneous depths), both KV layouts: every generated token must
    equal the token-by-token engine's, and all three jitted entry points
    must stay at cache size 1."""
    cfg, model, params = _model_params(arch)
    rng = np.random.default_rng(17)
    reqs = [
        (rng.integers(0, cfg.vocab_size, size=int(n)).tolist(), 4)
        for n in (5, 9, 3, 7, 6)
    ]
    with use_backend(backend):
        _, base = _serve(model, params, reqs)        # contiguous, unchunked
        for layout in ("contiguous", "paged"):
            kw = {"page_size": 4} if layout == "paged" else {}
            eng, got = _serve(model, params, reqs, layout=layout,
                              prefill_chunk=4, **kw)
            assert got == base, f"{layout} chunked diverges"
            assert eng._step_n._cache_size() == 1
            assert eng._admit._cache_size() == 1
            assert eng._prefill._cache_size() == 1
            assert eng.prefill_steps > 0


def test_mamba_prefill_block_matches_sequential_decode():
    """The recurrent-state unification at the block level: one chunked
    call of ``mamba_prefill_block`` (B*C-row GEMMs + one seeded SSD scan)
    must reproduce the token-sequential ``mamba_decode_block`` — per-row
    non-dividing widths, a zero-width row (carry untouched), carried
    state across consecutive chunks, both backends."""
    from repro.models import components as C

    cfg = _cfg("mamba2-2.7b")
    p = C.init_mamba(cfg, jax.random.PRNGKey(0))
    b, c = 3, 5
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 2 * c, cfg.d_model),
                          jnp.float32)
    widths = np.asarray([[5, 3, 0], [2, 5, 4]])
    for backend in BACKENDS:
        with use_backend(backend):
            ssm = jnp.zeros((b, cfg.ssm_heads, cfg.ssm_head_dim,
                             cfg.ssm_state), jnp.float32)
            conv = jnp.zeros((b, cfg.ssm_conv - 1, cfg.conv_dim), jnp.float32)
            s1, s2 = ssm, conv
            ys = []
            for t in range(2 * c):
                w = widths[t // c]
                y, n1, n2 = C.mamba_decode_block(cfg, p, x[:, t], s1, s2)
                vi = jnp.asarray(t % c < w)
                s1 = jnp.where(vi[:, None, None, None], n1, s1)
                s2 = jnp.where(vi[:, None, None], n2, s2)
                ys.append(y)
            ys = jnp.stack(ys, 1)
            c1, c2 = ssm, conv
            for k, w in enumerate(widths):
                xs = x[:, k * c : (k + 1) * c]
                valid = jnp.arange(c)[None, :] < jnp.asarray(w)[:, None]
                yc, c1, c2 = C.mamba_prefill_block(cfg, p, xs, c1, c2, valid)
                for r in range(b):
                    if w[r]:
                        np.testing.assert_allclose(
                            np.asarray(yc[r, : w[r]]),
                            np.asarray(ys[r, k * c : k * c + w[r]]),
                            rtol=2e-4, atol=2e-4,
                        )
            np.testing.assert_allclose(np.asarray(c1), np.asarray(s1),
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(np.asarray(c2), np.asarray(s2),
                                       rtol=2e-4, atol=2e-4)


def test_ssd_prefill_chunk_registered_and_tuned():
    """The chunked-SSD serving path is a first-class op: registered with
    both lowerings (coverage reports the port) and tunable — the SSD
    chunk size comes from the tuning table and any setting yields the
    same math (chunk invariance), clamped so short chunks never pad."""
    from repro.core.registry import clear_tuning, coverage, set_tuning
    from repro.kernels import ops

    assert coverage()["ssd_prefill_chunk"] is True
    b, s, h, p, n = 2, 7, 3, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, s, 1, n))
    C = jax.random.normal(ks[4], (b, s, 1, n))
    st = jax.random.normal(jax.random.PRNGKey(9), (b, h, p, n))
    y0, f0 = ops.ssd_prefill_chunk(x, dt, A, Bm, C, st)
    try:
        for chunk in (1, 3, 64):
            set_tuning("ssd_prefill_chunk", chunk=chunk)
            y, f = ops.ssd_prefill_chunk(x, dt, A, Bm, C, st)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(np.asarray(f), np.asarray(f0),
                                       rtol=2e-4, atol=2e-4)
    finally:
        clear_tuning()


@pytest.mark.parametrize("backend", BACKENDS)
def test_windowed_chunked_prefill_needs_paged(backend):
    """Sliding-window archs: chunking works on the paged layout (absolute
    positions, window applied as masking) and must be token-identical;
    the contiguous ring cache cannot host chunks and is rejected."""
    cfg = _cfg("mixtral-8x7b")
    cfg = dataclasses.replace(cfg, window=5)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        ServingEngine(model, params, batch=2, max_len=16, prefill_chunk=4)
    rng = np.random.default_rng(29)
    reqs = [
        (rng.integers(0, cfg.vocab_size, size=int(n)).tolist(), 4)
        for n in (8, 10, 6, 9)
    ]
    with use_backend(backend):
        _, base = _serve(model, params, reqs, layout="paged", page_size=4)
        _, got = _serve(model, params, reqs, layout="paged", page_size=4,
                        prefill_chunk=4)
    assert got == base


def test_prefill_accounting():
    """The host mirror's byproducts: every request gets a TTFT stamp, the
    ingested-prompt count is exact, and chunked ingestion takes the
    expected ceil(P/C) prefill steps for a lone request."""
    cfg, model, params = _model_params("qwen2.5-3b")
    toks = list(range(1, 10))
    eng = ServingEngine(model, params, batch=2, max_len=16,
                        prefill_chunk=4)
    rid = eng.submit(toks, 3)
    eng.run()
    # P=9, C=4: chunks of 4 and 4; the lone remaining prompt token is just
    # a decode feed, so the scheduler hands it to the fused decode path
    assert eng.prefill_steps == 2
    assert eng.prompt_tokens == len(toks)
    assert rid in eng.ttft and eng.ttft[rid] > 0


def test_sampling_invariant_to_chunk_schedule():
    """Sampled streams must not depend on the step schedule: subkeys are
    fold_in(admission key, position), so chunked ingestion (fewer steps to
    reach a position) draws the same tokens as token-by-token."""
    cfg, model, params = _model_params("qwen2.5-3b")
    reqs = [([3, 5, 7, 2, 9, 4], 5), ([11, 2, 8], 5), ([4, 4, 4, 4, 1], 5)]
    runs = {}
    for pc in (1, 4):
        _, runs[pc] = _serve(model, params, reqs, prefill_chunk=pc,
                             temperature=1.0, top_k=8, seed=42)
    assert runs[1] == runs[4]


def test_encdec_prefill_chunk_matches_decode():
    """encdec keeps signature parity: chunked ingestion reproduces the
    step-by-step decode logits and pos advance, including per-row widths."""
    cfg = dataclasses.replace(get_arch("seamless-m4t-medium").reduced(),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0,
                              cfg.vocab_size).astype(jnp.int32)
    s = model.init_decode_state(2, 12, per_row_pos=True)
    logits = {}
    for j in range(5):
        l, s = model.decode_step(params, s, toks[:, j])
        logits[j + 1] = l
    # uniform width 5
    s2 = model.init_decode_state(2, 12, per_row_pos=True)
    l2, s2 = model.prefill_chunk(params, s2, toks[:, :5],
                                 jnp.asarray([5, 5], jnp.int32))
    assert s2["pos"].tolist() == [5, 5]
    np.testing.assert_allclose(np.asarray(l2), np.asarray(logits[5]),
                               rtol=1e-5, atol=1e-5)
    # heterogeneous widths: row 1 ingests only 3 tokens
    s3 = model.init_decode_state(2, 12, per_row_pos=True)
    l3, s3 = model.prefill_chunk(params, s3, toks[:, :5],
                                 jnp.asarray([5, 3], jnp.int32))
    assert s3["pos"].tolist() == [5, 3]
    np.testing.assert_allclose(np.asarray(l3[1]), np.asarray(logits[3][1]),
                               rtol=1e-5, atol=1e-5)
