"""The whole run on the CPU at smoke size: sound runs are correct, the
lower-precision control and planted faults are not (CPU, Pallas in
interpret mode)."""
import json
import time

import tiny

import jax
import jax.numpy as jnp
import pytest

import calibrate
import derive
import harness

CELLS = {"dense.closed": ("tiny-dense", "tiny-closed"),
         "dense.chat": ("tiny-dense", "tiny-chat"),
         "twin.closed": ("tiny-twin", "tiny-closed")}
# float32 program against the float32 reference reads 0 at this size;
# the bfloat16 control read 5e-4 to 2.4e-3 on seeds 1 and 2
LIMIT = 1e-4


def add_twin_family(root):
    """A family added as files alone: ``families/twin.py``, a dense twin
    under another name, and a configuration that names it."""
    (root / "families" / "twin.py").write_text(
        (tiny.CHIP / "families" / "dense.py").read_text())
    cfg = {**tiny.CONFIGS["tiny-dense"], "family": "twin"}
    (root / "configs" / "tiny-twin.json").write_text(json.dumps(cfg))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench"),
                          {n: {"max_gap_limit": LIMIT} for n in CELLS})
    add_twin_family(root)
    return root


def _cell(root, name):
    return harness.load_cell(name, root, tiny.bench(CELLS))


def _run(root, name, seed=2 ** 31 + 3):
    return harness.run_cell(_cell(root, name), seed, 1.0, False,
                            t_process=time.perf_counter(), peaks=tiny.PEAKS,
                            log=lambda s: None)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(root, name):
    out = _run(root, name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s", "output_tok_s"}
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", sorted(CELLS))
def test_every_request_of_the_window_completes(root, name):
    run, _, _ = harness.serve(_cell(root, name), 9, 1.0, trace_dir=None,
                              t_process=time.perf_counter(), peaks=tiny.PEAKS)
    assert run.recs and len(run.done()) == len(run.recs)
    assert all(run.t_start < r.t_done <= run.t_end for r in run.recs)
    assert derive.output_tokens(run) == sum(r.max_new for r in run.recs)
    # a mix that states its requests submits exactly that many
    if "requests" in run.cell.mix:
        assert len(run.recs) == run.cell.mix["requests"]


def test_a_family_added_as_files_resolves_to_its_own_module(root):
    """``twin.closed`` serves above through ``families/twin.py`` alone."""
    from pathlib import Path
    cell = _cell(root, "twin.closed")
    assert Path(cell.family.__file__) == \
        (root / "families" / "twin.py").resolve()
    assert cell.family is not _cell(root, "dense.closed").family


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_caught(root, name):
    r = calibrate.readings(_cell(root, name), 4, 1.0, tiny.PEAKS, LIMIT)
    assert r["program_gap"] <= LIMIT < r["control_gap"], r
    assert r["program_correct"] and not r["control_correct"]


def test_decode_that_leaves_its_state_unchanged_is_caught(root, monkeypatch):
    from repro.models import lm
    real = lm.decode_step

    def unchanged(cfg, params, state, token, **kw):
        logits, _ = real(cfg, params, state, token, **kw)
        return logits, state

    monkeypatch.setattr(lm, "decode_step", unchanged)
    jax.clear_caches()
    out = _run(root, "dense.closed")
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > LIMIT


def test_half_of_the_batch_left_out_is_caught(root, monkeypatch):
    from repro.models import lm
    real = lm.decode_step

    def half(cfg, params, state, token, **kw):
        logits, new = real(cfg, params, state, token, **kw)
        b = logits.shape[0]
        return logits[jnp.arange(b) % max(1, b // 2)], new

    monkeypatch.setattr(lm, "decode_step", half)
    jax.clear_caches()
    out = _run(root, "dense.closed")
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > LIMIT


def test_altered_token_is_caught(root, monkeypatch):
    from repro.serving import engine
    real = engine._sample

    def altered(logits, *a, **kw):
        return (real(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "_sample", altered)
    jax.clear_caches()
    out = _run(root, "dense.closed")
    assert not out["correct"]


def test_answer_that_never_comes_is_caught(root, monkeypatch):
    real = harness.Driver.closed

    def one_left(self, *a):
        real(self, *a)
        self.submit()       # taken, never served

    monkeypatch.setattr(harness.Driver, "closed", one_left)
    out = _run(root, "dense.closed")
    assert not out["correct"]
    assert out["checks"]["unfinished"]["value"] > 0
