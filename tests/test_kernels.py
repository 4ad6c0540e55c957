"""Per-kernel correctness sweeps: every Pallas kernel (interpret mode on
CPU) against its ref.py oracle across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import set_tuning, clear_tuning
from repro.kernels import ref
from repro.kernels.eltwise import bias_add_rows_pallas, relu_bwd_pallas, relu_pallas
from repro.kernels.flash_attention import (
    flash_attention_bwd_pallas,
    flash_attention_pallas,
    flash_decode_pallas,
)
from repro.kernels.gemm import gemm_pallas
from repro.kernels.im2col import col2im_pallas, im2col_pallas
from repro.kernels.mamba_scan import ssd_scan_pallas
from repro.kernels.pooling import maxpool_bwd_pallas, maxpool_pallas
from repro.kernels.rmsnorm import rmsnorm_bwd_pallas, rmsnorm_pallas
from repro.kernels.softmax_xent import (
    softmax_pallas,
    softmax_xent_bwd_pallas,
    softmax_xent_pallas,
)


@pytest.fixture(autouse=True)
def _clear():
    clear_tuning()
    yield
    clear_tuning()


def key(i=0):
    return jax.random.PRNGKey(i)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "m,k,n", [(128, 128, 128), (200, 300, 170), (7, 5, 3), (256, 512, 384),
              (1, 1024, 8), (129, 257, 129),
              # planned blocks: M of 1, 16 and 2048; K = 128 * 43 (like
              # 11008); N = 128 * 37 (like 151936), tiled with an edge
              # block; K under 128; an edge block along M; a K that is
              # no multiple of 128 and too long to take whole (padded)
              (1, 256, 384), (16, 5504, 256), (2048, 256, 4736),
              (16, 96, 4736), (2100, 128, 384), (8, 30001, 128)]
)
def test_gemm_shapes(m, k, n):
    a = jax.random.normal(key(0), (m, k), jnp.float32)
    b = jax.random.normal(key(1), (k, n), jnp.float32)
    np.testing.assert_allclose(
        gemm_pallas(a, b), ref.gemm(a, b), rtol=1e-4, atol=1e-4 * np.sqrt(k)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_dtypes(dtype):
    a = jax.random.normal(key(0), (256, 256), dtype)
    b = jax.random.normal(key(1), (256, 128), dtype)
    got = np.asarray(gemm_pallas(a, b), np.float32)
    want = np.asarray(ref.gemm(a, b), np.float32)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 16)


def test_gemm_tuning_registry():
    set_tuning("gemm", bm=32, bn=64, bk=32)
    a = jax.random.normal(key(0), (100, 96), jnp.float32)
    b = jax.random.normal(key(1), (96, 72), jnp.float32)
    np.testing.assert_allclose(gemm_pallas(a, b), ref.gemm(a, b), rtol=1e-4,
                               atol=1e-3)


# qwen2.5-3b's projections at 16 rows (decode) and 16 x 128 (chunked
# prefill), and its tied head at 16 rows
_CELL_WIDTHS = [(2048, 2048), (2048, 256), (2048, 11008), (11008, 2048)]
_CELL_SHAPES = ([(16, k, n) for k, n in _CELL_WIDTHS]
                + [(2048, k, n) for k, n in _CELL_WIDTHS]
                + [(16, 2048, 151936)])


def test_gemm_plan_at_cell_shapes():
    from repro.core import last_plan, resolved_plans
    from repro.kernels.gemm import VMEM_BUDGET, plan_blocks, vmem_bytes

    steps = {}
    for m, k, n in _CELL_SHAPES:
        p = plan_blocks(m, k, n, 2, 2)
        assert k % p.bk == 0, (m, k, n, p)
        assert vmem_bytes(p.bm, p.bn, p.bk, 2, 2) <= VMEM_BUDGET, (m, k, n, p)
        # the call pads and copies no operand, and records its plan
        jaxpr = str(jax.make_jaxpr(
            lambda a, b: gemm_pallas(a, b, interpret=False))(
                jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
                jax.ShapeDtypeStruct((k, n), jnp.bfloat16)))
        assert " pad[" not in jaxpr and "copy" not in jaxpr, (m, k, n)
        assert resolved_plans("gemm")[(m, k, n)] == p
        assert last_plan("gemm") == p._asdict()
        steps[(m, k, n)] = p.steps

    def program(m):  # q, k, v, o, gate, up, down of 36 layers, and the head
        q, kv, up, down = (steps[(m, k, n)] for k, n in _CELL_WIDTHS)
        return 36 * (2 * q + 2 * kv + 2 * up + down) + steps[(16, 2048, 151936)]

    # a tenth of the grid steps of 128 x 128 x 128 blocks, or fewer
    assert program(2048) <= 30_000
    assert program(16) <= 5_000


@pytest.mark.parametrize("m,k,n", _CELL_SHAPES)
def test_gemm_plan_at_cell_shapes_as_before(m, k, n, monkeypatch):
    """The VMEM term for output lanes no multiple of 256 leaves qwen2.5-3b's
    plans as they were: each is the plan of the former count, which took
    two f32 blocks whatever the lanes."""
    from repro.kernels import gemm as G

    plan = G.plan_blocks(m, k, n, 2, 2)
    monkeypatch.setattr(
        G, "vmem_bytes", lambda bm, bn, bk, item, out_item:
        2 * (bm * bk + bk * bn) * item + 8 * bm * bn + 2 * bm * bn * out_item)
    assert G._least_time(m, k, n, 2, 2) == plan


def test_autotune_sweeps_gemm_around_its_plan():
    """gemm's blocks come from its plan, not literal defaults: the sweep
    still runs, around the plan each case's baseline resolved."""
    from repro.tuning import table as tt
    from repro.tuning.autotune import run_autotune

    lines = []
    doc = run_autotune(smoke=True, only=["gemm"], repeats=1, log=lines.append)
    assert tt.validate(doc) == []
    assert {c["op"]: c["status"] for c in doc["cells"]}["matmul"] == "swept"
    assert not any("no derivable knobs" in line for line in lines)
    assert any("gemm[" in line for line in lines), lines


# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "n,c,h,w,kh,kw,s,p",
    [(2, 3, 8, 9, 3, 3, 1, 0), (2, 3, 8, 9, 3, 3, 1, 1),
     (1, 1, 28, 28, 5, 5, 1, 0), (2, 4, 10, 10, 2, 3, 2, 1),
     (2, 2, 7, 7, 3, 3, 3, 0)],
)
def test_im2col(n, c, h, w, kh, kw, s, p):
    x = jax.random.normal(key(0), (n, c, h, w), jnp.float32)
    np.testing.assert_array_equal(
        im2col_pallas(x, kh, kw, s, p), ref.im2col(x, kh, kw, s, p)
    )


@pytest.mark.parametrize(
    "n,c,h,w,kh,kw,p", [(2, 3, 8, 9, 3, 3, 0), (2, 3, 8, 9, 3, 3, 1),
                        (1, 2, 12, 12, 5, 5, 2)]
)
def test_col2im(n, c, h, w, kh, kw, p):
    oh = ref.conv_out_size(h, kh, 1, p)
    ow = ref.conv_out_size(w, kw, 1, p)
    cols = jax.random.normal(key(0), (n, c * kh * kw, oh * ow), jnp.float32)
    np.testing.assert_allclose(
        col2im_pallas(cols, (n, c, h, w), kh, kw, 1, p),
        ref.col2im(cols, (n, c, h, w), kh, kw, 1, p),
        rtol=1e-6, atol=1e-6,
    )


# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "n,c,h,w,k,s,p",
    [(2, 3, 8, 8, 2, 2, 0), (2, 3, 9, 9, 2, 2, 0), (1, 4, 28, 28, 2, 2, 0),
     (2, 2, 12, 12, 3, 3, 0), (1, 1, 8, 8, 2, 2, 1)],
)
def test_maxpool(n, c, h, w, k, s, p):
    x = jax.random.normal(key(0), (n, c, h, w), jnp.float32)
    out, arg = maxpool_pallas(x, k, s, p)
    rout, rarg = ref.maxpool(x, k, s, p)
    np.testing.assert_allclose(out, rout)
    np.testing.assert_array_equal(arg, rarg)
    dy = jax.random.normal(key(1), out.shape)
    np.testing.assert_allclose(
        maxpool_bwd_pallas(dy, arg, (n, c, h, w), k, s, p),
        ref.maxpool_bwd(dy, rarg, (n, c, h, w), k, s, p),
    )


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,v", [(4, 10), (130, 17), (256, 1000), (5, 7)])
def test_softmax_xent(b, v):
    x = jax.random.normal(key(0), (b, v), jnp.float32) * 3
    y = jax.random.randint(key(1), (b,), 0, v)
    np.testing.assert_allclose(softmax_pallas(x), ref.softmax(x),
                               rtol=1e-5, atol=1e-6)
    l, p = softmax_xent_pallas(x, y)
    rl, rp = ref.softmax_xent(x, y)
    np.testing.assert_allclose(l, rl, rtol=1e-5)
    np.testing.assert_allclose(p, rp, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        softmax_xent_bwd_pallas(p, y), ref.softmax_xent_bwd(rp, y),
        rtol=1e-5, atol=1e-7,
    )


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("r,d", [(8, 64), (300, 128), (17, 96)])
def test_rmsnorm(r, d):
    x = jax.random.normal(key(0), (r, d), jnp.float32)
    w = jax.random.normal(key(1), (d,))
    np.testing.assert_allclose(rmsnorm_pallas(x, w), ref.rmsnorm(x, w),
                               rtol=1e-5, atol=1e-6)
    dy = jax.random.normal(key(2), (r, d))
    dx, dw = rmsnorm_bwd_pallas(x, w, dy)
    gx, gw = jax.grad(lambda x, w: (ref.rmsnorm(x, w) * dy).sum(), (0, 1))(x, w)
    np.testing.assert_allclose(dx, gx, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dw, gw, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,sq,sk,hq,hkv,d,causal,window",
    [(1, 32, 32, 4, 2, 16, True, None), (2, 33, 33, 4, 4, 16, True, None),
     (1, 48, 48, 8, 2, 32, True, 20), (2, 16, 16, 2, 1, 8, False, None)],
)
def test_flash_attention(b, sq, sk, hq, hkv, d, causal, window):
    set_tuning("flash_attention", bq=16, bk=16)
    q = jax.random.normal(key(0), (b, sq, hq, d), jnp.float32)
    k = jax.random.normal(key(1), (b, sk, hkv, d), jnp.float32)
    v = jax.random.normal(key(2), (b, sk, hkv, d), jnp.float32)
    o, lse = flash_attention_pallas(q, k, v, causal=causal, window=window)
    want = ref.mha_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(o, want, rtol=2e-4, atol=2e-4)
    do = jax.random.normal(key(3), o.shape)
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, o, lse, do, causal=causal, window=window
    )
    f = lambda q, k, v: (
        ref.mha_attention(q, k, v, causal=causal, window=window) * do
    ).sum()
    gq, gk, gv = jax.grad(f, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(dq, gq, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(dk, gk, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(dv, gv, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize(
    "b,hq,hkv,d,smax,ln,window",
    [(2, 4, 2, 16, 64, 37, None), (1, 8, 8, 32, 128, 128, None),
     (2, 4, 1, 16, 96, 50, 24)],
)
def test_flash_decode(b, hq, hkv, d, smax, ln, window):
    set_tuning("flash_decode", bk=16)
    q = jax.random.normal(key(0), (b, hq, d), jnp.float32)
    kc = jax.random.normal(key(1), (b, smax, hkv, d), jnp.float32)
    vc = jax.random.normal(key(2), (b, smax, hkv, d), jnp.float32)
    o = flash_decode_pallas(q, kc, vc, jnp.int32(ln), window=window)
    want = ref.mha_attention(
        q[:, None], kc[:, :ln], vc[:, :ln], causal=True, window=window,
        q_offset=ln - 1,
    )[:, 0]
    np.testing.assert_allclose(o, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,S,H,P,N,chunk", [(2, 32, 4, 8, 16, 8), (1, 37, 3, 16, 32, 16),
                        (2, 64, 2, 8, 8, 64), (2, 1, 3, 8, 16, 64)]
)
def test_ssd_scan(B, S, H, P, N, chunk):
    set_tuning("ssd_scan", chunk=chunk)
    x = jax.random.normal(key(0), (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(key(1), (B, S, H)))
    A = -jnp.exp(jax.random.normal(key(2), (H,)))
    Bm = jax.random.normal(key(3), (B, S, 1, N))
    C = jax.random.normal(key(4), (B, S, 1, N))
    y, hf = ssd_scan_pallas(x, dt, A, Bm, C, chunk=chunk)
    ry, rhf = ref.ssd_scan(x, dt, A, Bm, C, chunk=chunk)
    np.testing.assert_allclose(y, ry, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(hf, rhf, rtol=2e-4, atol=2e-4)


def test_ssd_pallas_rejects_grouped_bc():
    """Grouped B/C has no Pallas lowering: the serving dispatch raises
    instead of quietly running the reference scan."""
    from repro.core.policy import use_backend
    from repro.kernels import ops

    B, S, H, P, N = 1, 4, 4, 8, 8
    x = jax.random.normal(key(0), (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(key(1), (B, S, H)))
    A = -jnp.exp(jax.random.normal(key(2), (H,)))
    Bm = jax.random.normal(key(3), (B, S, 2, N))
    state = jnp.zeros((B, H, P, N))
    with use_backend("pallas"), pytest.raises(ValueError, match="n_groups"):
        ops.ssd_prefill_chunk(x, dt, A, Bm, Bm, state)


def test_ssd_matches_sequential_decode():
    B, S, H, P, N = 1, 12, 2, 4, 8
    x = jax.random.normal(key(0), (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(key(1), (B, S, H)))
    A = -jnp.exp(jax.random.normal(key(2), (H,)))
    Bm = jax.random.normal(key(3), (B, S, 1, N))
    C = jax.random.normal(key(4), (B, S, 1, N))
    y, fin = ref.ssd_scan(x, dt, A, Bm, C, chunk=4)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        yt, state = ref.ssd_decode_step(
            x[:, t], dt[:, t], A, Bm[:, t], C[:, t], state
        )
        ys.append(yt)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(fin, state, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
def test_eltwise():
    x = jax.random.normal(key(0), (70, 130), jnp.float32)
    np.testing.assert_array_equal(relu_pallas(x), ref.relu(x))
    np.testing.assert_array_equal(relu_pallas(x, 0.1), ref.relu(x, 0.1))
    dy = jax.random.normal(key(1), x.shape)
    np.testing.assert_array_equal(
        relu_bwd_pallas(x, dy, 0.1), ref.relu_bwd(x, dy, 0.1)
    )
    v = jax.random.normal(key(2), (130,))
    np.testing.assert_allclose(
        bias_add_rows_pallas(x, v), ref.bias_add_rows(x, v), rtol=1e-6
    )
