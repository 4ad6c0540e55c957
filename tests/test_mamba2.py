"""The Mamba-2 block as published, in the program (CPU, smoke size): the
conv window covers x|B|C wherever recurrent state is kept, the residual
is carried in float32 where the model says so, the SSD kernel is named by
phase, the engine counts its recurrent state, and the parameter count is
the parameters'."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.core.policy import use_backend
from repro.models import components as C
from repro.models import lm
from repro.models.model import build_model


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-2.7b"])
def test_param_count_is_the_parameters(arch):
    for cfg in (get_arch(arch), get_arch(arch).reduced()):
        shapes = jax.eval_shape(build_model(cfg).init_params,
                                jax.random.PRNGKey(0))
        assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
            == cfg.param_count(), cfg.name


def test_conv_state_covers_x_b_and_c():
    from repro.serving.config import SpecConfig
    from repro.serving.drafter import make_drafter

    ssm = get_arch("mamba2-2.7b-smoke")
    window = (ssm.ssm_conv - 1, ssm.d_inner + 2 * ssm.ssm_state)
    assert ssm.conv_dim == window[1]
    p = C.init_mamba(ssm, jax.random.PRNGKey(0))
    assert p["conv_w"].shape == (ssm.ssm_conv, window[1])
    assert p["conv_b"].shape == (window[1],)
    st = build_model(ssm).init_decode_state(
        3, 32, per_row_pos=True, layout="paged", page_size=8, snapshots=True)
    assert st["conv"].shape == (ssm.n_layers, 3) + window
    assert st["snap_conv"].shape[2:] == window
    hyb = get_arch("zamba2-2.7b-smoke")
    drafter = make_drafter(SpecConfig(k=2, drafter="hybrid_ssm"), hyb)
    assert drafter.init_state(3)["drf_conv"].shape == \
        (hyb.n_layers, 3, hyb.ssm_conv - 1, hyb.conv_dim)


def _carry_dtypes(cfg):
    """Dtypes of the carries of the decode step's scans over layers."""
    model = build_model(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: model.init_decode_state(
        2, 16, per_row_pos=True))
    jaxpr = jax.make_jaxpr(lambda p, s, t: lm.decode_step(cfg, p, s, t))(
        params, state, jax.ShapeDtypeStruct((2,), jnp.int32))
    return {str(e.outvars[0].aval.dtype) for e in jaxpr.eqns
            if e.primitive.name == "scan"}


@pytest.mark.parametrize("residual_f32,want", [(True, "float32"),
                                               (False, "bfloat16")])
def test_the_residual_crosses_the_layers_in_its_dtype(residual_f32, want):
    """Traced only (XLA:CPU runs no bf16 batched dot): the served dtype
    bfloat16, the residual stream float32 where ``residual_f32``."""
    cfg = dataclasses.replace(get_arch("mamba2-2.7b-smoke"), dtype="bfloat16",
                              residual_f32=residual_f32)
    assert get_arch("mamba2-2.7b").residual_f32
    assert _carry_dtypes(cfg) == {want}


@pytest.mark.parametrize("c,name", [(1, "repro_ssd_decode"),
                                    (8, "repro_ssd_prefill_chunk")])
def test_the_serving_ssd_kernel_is_named_by_phase(c, name):
    cfg = get_arch("mamba2-2.7b-smoke")
    p = C.init_mamba(cfg, jax.random.PRNGKey(0))
    with use_backend("pallas"):
        jaxpr = jax.make_jaxpr(
            lambda x, s1, s2, v: C.mamba_prefill_block(cfg, p, x, s1, s2, v))(
            jnp.zeros((2, c, cfg.d_model)),
            jnp.zeros((2, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)),
            jnp.zeros((2, cfg.ssm_conv - 1, cfg.conv_dim)),
            jnp.ones((2, c), bool))
        train = jax.make_jaxpr(lambda x: C.mamba_block(cfg, p, x))(
            jnp.zeros((2, 8, cfg.d_model)))
    assert set(re.findall(r"repro_ssd\w*", str(jaxpr))) == {name}
    assert set(re.findall(r"repro_ssd\w*", str(train))) == {"repro_ssd_scan"}


def test_stats_count_the_recurrent_state():
    from repro.serving import CacheConfig, EngineConfig, ServingEngine

    cfg = get_arch("mamba2-2.7b-smoke")
    model = build_model(cfg)
    eng = ServingEngine(model, model.init_params(jax.random.PRNGKey(0)),
                        batch=3, max_len=32, cache=CacheConfig(),
                        config=EngineConfig(prefill_chunk=8))
    ssm = cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
    conv = (cfg.ssm_conv - 1) * cfg.conv_dim * 4          # float32 at smoke
    assert eng.stats()["recurrent_state_bytes"] == \
        3 * cfg.n_layers * (ssm + conv)
    dense = build_model(get_arch("qwen2.5-3b-smoke"))
    eng = ServingEngine(dense, dense.init_params(jax.random.PRNGKey(0)),
                        batch=3, max_len=32)
    assert "recurrent_state_bytes" not in eng.stats()
