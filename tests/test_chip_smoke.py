"""``chip_smoke.py`` stays runnable: its serve-and-check path at smoke
size on CPU, its refusal to report a result off the chip, and the
compile-cache placement it starts with."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_and_check_at_smoke_size(capsys):
    chip_smoke = _load_chip_smoke()
    report = chip_smoke.serve_and_check(
        "qwen2.5-3b-smoke", n_requests=4, prompt_lens=(8, 40), gen=4,
        page_size=4, prefill_chunk=16, steps_per_sync=4)
    assert report["param_bytes"] > 0
    # the paged engine turns the "host" tier on: a second pool as large
    assert report["host_tier_bytes"] == report["kv_pool_bytes"] > 0
    assert report["max_logit_err"] <= report["logit_tol"]
    assert report["max_emitted_shortfall"] <= report["emitted_bound"]
    assert set(report["compile_s"]) >= {"_step_n", "_prefill", "_admit",
                                         "_release"}
    assert "logits vs reference" in capsys.readouterr().out


def test_serve_and_check_catches_swapped_block_tables(monkeypatch):
    """A fault in the engine's batched path that the batch-1 cache-path
    witness cannot see: rows 0 and 1 read and write each other's KV pages
    in every batched decode step.  The emitted-token check must fail."""
    chip_smoke = _load_chip_smoke()
    from repro.models import lm

    decode_step = lm.decode_step

    def swapped(cfg, params, state, token, **kw):
        bt = state["block_table"]
        if bt.shape[0] < 2:
            return decode_step(cfg, params, state, token, **kw)
        perm = jnp.arange(bt.shape[0]).at[0].set(1).at[1].set(0)
        logits, new = decode_step(cfg, params, {**state, "block_table":
                                                bt[perm]}, token, **kw)
        return logits, {**new, "block_table": new["block_table"][perm]}

    monkeypatch.setattr(lm, "decode_step", swapped)
    with pytest.raises(AssertionError, match="engine emitted tokens"):
        chip_smoke.serve_and_check(
            "qwen2.5-3b-smoke", n_requests=4, prompt_lens=(8, 40), gen=4,
            page_size=4, prefill_chunk=16, steps_per_sync=4)


def test_logit_tolerance_grows_with_depth_and_count():
    chip_smoke = _load_chip_smoke()
    from repro.configs.registry import get_arch

    cfg = get_arch("qwen2.5-3b")
    tol = chip_smoke.logit_tolerance(cfg, 1.0, 10**6)
    shallow = chip_smoke.logit_tolerance(cfg.reduced(), 1.0, 10**6)
    assert shallow < tol < 1.0
    assert chip_smoke.logit_tolerance(cfg, 1.0, 10**3) < tol


def test_entry_point_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    cc.reset_cache()


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = compile_cache.enable_compile_cache()
    assert where == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == where


def test_compile_cache_leaves_env_to_jax(monkeypatch, restore_cache_dir,
                                         tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
