"""Mosaic compiles of the serving path's kernels at published widths.

Each test lowers one Pallas kernel with ``interpret=False`` for one chip
of a described (not attached) TPU v5e and compiles it, so a block that
breaks the (8, 128) tiling rule or a kernel that overflows VMEM fails
here, at no chip time.  Widths are qwen2.5-3b's (d_model 2048, 16/2
heads of 128, d_ff 11008, vocab 151936, page 16, prefill chunk 128,
batch 8) and, for the SSD scan, mamba2-2.7b's (80 heads of 64, state
128).  Nothing runs: a passing compile says nothing about results.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import (
    flash_attention_bwd_pallas,
    flash_attention_pallas,
    flash_decode_paged_pallas,
    flash_decode_paged_quant_pallas,
    flash_prefill_chunk_paged_pallas,
)
from repro.kernels.gemm import gemm_pallas
from repro.kernels.mamba_scan import ssd_scan_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas

B, C, D_MODEL, HQ, HKV, HD, D_FF, VOCAB = 8, 128, 2048, 16, 2, 128, 11008, 151936
PAGE, MAX_BLOCKS = 16, 35                 # 512-token prompt + 32 new, paged
N_PAGES = B * MAX_BLOCKS
SSM_H, SSM_P, SSM_N = 80, 64, 128         # mamba2-2.7b: d_inner 5120 / 64
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except RuntimeError as e:
        # JAX raises this one only when no TPU library is installed; any
        # other failure to describe the chip is a failure of these tests
        if "TPU support not installed" not in str(e):
            raise
        pytest.skip(f"no TPU compiler in this installation: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; assert a Mosaic kernel is in
    the program (interpret mode would have none)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m,k,n", [
    (B, D_MODEL, HQ * HD),       # decode q projection
    (B, D_MODEL, VOCAB),         # tied LM head
    (B * C, D_MODEL, D_FF),      # prefill MLP up/gate
], ids=["decode_proj", "lm_head", "prefill_mlp"])
def test_gemm(one_chip, m, k, n):
    _compile(lambda a, b: gemm_pallas(a, b, interpret=False), one_chip,
             ((m, k), BF16), ((k, n), BF16))


@pytest.mark.parametrize("rows", [B, B * C], ids=["decode", "prefill"])
def test_rmsnorm(one_chip, rows):
    _compile(lambda x, w: rmsnorm_pallas(x, w, interpret=False), one_chip,
             ((rows, D_MODEL), BF16), ((D_MODEL,), BF16))


def test_flash_decode_paged(one_chip):
    _compile(
        lambda q, k, v, n, bt: flash_decode_paged_pallas(
            q, k, v, n, bt, interpret=False),
        one_chip,
        ((B, HQ, HD), BF16), ((N_PAGES, PAGE, HKV, HD), BF16),
        ((N_PAGES, PAGE, HKV, HD), BF16), ((B,), I32), ((B, MAX_BLOCKS), I32),
    )


def test_flash_prefill_chunk_paged(one_chip):
    _compile(
        lambda q, k, v, s, w, bt: flash_prefill_chunk_paged_pallas(
            q, k, v, s, w, bt, interpret=False),
        one_chip,
        ((B, C, HQ, HD), BF16), ((N_PAGES, PAGE, HKV, HD), BF16),
        ((N_PAGES, PAGE, HKV, HD), BF16), ((B,), I32), ((B,), I32),
        ((B, MAX_BLOCKS), I32),
    )


def test_flash_decode_paged_quant(one_chip):
    _compile(
        lambda q, k, v, ks, vs, n, bt: flash_decode_paged_quant_pallas(
            q, k, v, ks, vs, n, bt, interpret=False),
        one_chip,
        ((B, HQ, HD), BF16), ((N_PAGES, PAGE, HKV, HD), jnp.int8),
        ((N_PAGES, PAGE, HKV, HD), jnp.int8), ((N_PAGES, HKV), F32),
        ((N_PAGES, HKV), F32), ((B,), I32), ((B, MAX_BLOCKS), I32),
    )


S_TRAIN = 512


def test_flash_attention_fwd(one_chip):
    _compile(
        lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
        one_chip,
        ((1, S_TRAIN, HQ, HD), BF16), ((1, S_TRAIN, HKV, HD), BF16),
        ((1, S_TRAIN, HKV, HD), BF16),
    )


def test_flash_attention_bwd(one_chip):
    q = ((1, S_TRAIN, HQ, HD), BF16)
    kv = ((1, S_TRAIN, HKV, HD), BF16)
    _compile(
        lambda q, k, v, o, lse, do: flash_attention_bwd_pallas(
            q, k, v, o, lse, do, interpret=False),
        one_chip, q, kv, kv, q, ((1, HQ, S_TRAIN), F32), q,
    )


@pytest.mark.parametrize("s", [1, C], ids=["decode", "prefill_chunk"])
def test_ssd_scan(one_chip, s):
    _compile(
        lambda x, dt, a, b_, c_, h: ssd_scan_pallas(
            x, dt, a, b_, c_, chunk=C, initial_state=h, interpret=False,
            tuning_op="ssd_prefill_chunk"),
        one_chip,
        ((B, s, SSM_H, SSM_P), BF16), ((B, s, SSM_H), F32), ((SSM_H,), F32),
        ((B, s, 1, SSM_N), BF16), ((B, s, 1, SSM_N), BF16),
        ((B, SSM_H, SSM_P, SSM_N), F32),
    )
