"""The engine's spans, scopes and counters read from a trace: the
synthetic trace of ``test_bench_trace`` with engine spans and op scopes
added, and a traced window on the CPU (CPU)."""
import time

import tiny

import pytest

import engine_trace as E
import harness
import trace_reduce as T
from test_bench_trace import _trace

# the operations of ``_trace`` inside its two step programs, as
# ``capture`` keeps them: (scope, name, t0, t1)
SCOPED = [("attention", "repro_gemm", 100, 300), ("", "repro_gemm", 250, 400),
          ("layers", "while", 100, 400),
          ("attention", "repro_flash_decode_paged", 600, 900),
          ("kv_pool", "copy", 950, 1000)]
# two cycles inside the harness's two ``bench.step`` spans
SPANS = [("engine.step", 10, 480), ("engine.decode", 20, 80),
         ("engine.harvest", 100, 450),
         ("engine.step", 610, 1190), ("engine.prefill", 620, 640),
         ("engine.harvest", 650, 1000), ("engine.release", 1010, 1050)]


def _extra():
    return E.Extra(spans=list(SPANS), scoped={"/device:TPU:0": list(SCOPED)})


def test_host_time_per_cycle_leaves_out_the_harvest():
    # (470 - 350) and (580 - 350) ns
    assert E.host_ms_per_cycle(_trace(), _extra()) == pytest.approx(175e-6)


def test_scope_share_of_the_step_programs():
    tr, ex = _trace(), _extra()
    # programs: 300 + 400 ns; control flow left out
    assert E.programs_s(tr) == pytest.approx(700e-9)
    assert E.scope_s(tr, ex) == pytest.approx(
        {"attention": 500e-9, "": 150e-9, "kv_pool": 50e-9})
    assert E.scope_pct(tr, ex, "kv_pool") == pytest.approx(100 * 50 / 700)
    assert E.scope_pct(tr, ex, "mlp") == 0.0
    assert E.unscoped_ops(tr, ex) == [["repro_gemm", pytest.approx(150e-9)]]
    tr.window = (0, 800)
    assert E.scope_s(tr, ex) == pytest.approx(
        {"attention": 400e-9, "": 150e-9})


def test_idle_time_goes_to_the_innermost_span():
    by = E.idle_by_span(_trace(), _extra())
    # gaps [0, 90], [420, 600], [900, 950], [1000, 1200], cut by the spans
    assert by == pytest.approx({
        "engine.step": 200e-9, "bench.wait": 100e-9, "engine.decode": 60e-9,
        "engine.harvest": 80e-9, "engine.release": 40e-9,
        "bench.step": 40e-9})
    assert sum(by.values()) == pytest.approx(
        sum(g[1] for g in T.idle_gaps(_trace(), n=100)))


def test_harness_readings_ignore_the_additions():
    tr, ex = _trace(), _extra()
    E.host_ms_per_cycle(tr, ex)
    E.scope_pct(tr, ex, "kv_pool")
    E.idle_by_span(tr, ex)
    assert T.busy_s(tr) == pytest.approx(680e-9)
    assert T.kernel_s(tr, "repro_gemm") == pytest.approx(350e-9)
    assert T.program_s(tr, "_step_n") == pytest.approx(300e-9)
    assert T.top_ops(tr) == T.top_ops(_trace())
    assert T.idle_gaps(tr) == T.idle_gaps(_trace())


def test_a_program_without_spans_scopes_or_counters_reads_none():
    tr = _trace()
    bare = E.Extra(scoped={"/device:TPU:0": [("",) + e[1:] for e in SCOPED]})
    assert E.host_ms_per_cycle(tr, bare) is None
    assert E.scope_pct(tr, bare, "kv_pool") is None
    assert set(E.idle_by_span(tr, bare)) == {"bench.step", "bench.wait"}
    assert E.slot_use_pct({"decode_steps": 8.0}, "decode_positions",
                          "decode_row_steps") is None
    assert E.slot_use_pct({"decode_positions": 3.0, "decode_row_steps": 4.0},
                          "decode_positions", "decode_row_steps") == 75.0


@pytest.mark.parametrize("stack, scope", [
    ("jit(_step_n)/while/body/layers/while/body/attention/kv_pool/transpose",
     "kv_pool"),
    ("jit(_prefill_step)/layers/while/body/mlp/dot_general", "mlp"),
    ("jit(_step_n)/while/body/layers/while/body/dynamic_slice", "layers"),
    ("jit(_admit)/broadcast_in_dim", ""),
])
def test_scope_is_the_innermost_known_name(stack, scope):
    assert E.scope_of(stack) == scope


def test_a_traced_cpu_window_holds_the_engine_spans(tmp_path):
    """The harness's capture keeps its own spans only; the engine's are
    read beside them from the same file, every cycle with its parts."""
    root = tiny.make_root(tmp_path, {"dense.closed": {"max_gap_limit": 1e-4}})
    cell = harness.load_cell("dense.closed", root,
                             tiny.bench({"dense.closed": ("tiny-dense",
                                                          "tiny-closed")}))
    trace_dir = tmp_path / "trace"
    run, _, _ = harness.serve(cell, 2 ** 31 + 5, 1.0, trace_dir=trace_dir,
                              t_process=time.perf_counter(), peaks=tiny.PEAKS)
    tr, ex = T.capture(trace_dir), E.capture(trace_dir, {})
    assert {h[0] for h in tr.host} <= {"bench.submit", "bench.step"}
    names = [s[0] for s in ex.spans]
    assert set(names) >= {"engine.step", "engine.refill", "engine.prefill",
                          "engine.decode", "engine.harvest"}
    assert names.count("engine.step") == len(run.cycles)
    assert names.count("engine.harvest") == len(run.cycles)
    assert E.host_ms_per_cycle(tr, ex) > 0


_DUMPED = """
import jax, jax.numpy as jnp

def _step_n(x):
    with jax.named_scope("layers"):
        with jax.named_scope("kv_pool"):
            t = jnp.sin(x.T) + 1
        with jax.named_scope("mlp"):
            return jnp.tanh(t @ x)

def other(x):
    return x + 1

x = jnp.ones((64, 64))
jax.jit(_step_n)(x).block_until_ready()
jax.jit(other)(x).block_until_ready()
"""


def test_scopes_come_from_the_dumped_programs(tmp_path):
    """``DUMP_FLAGS`` dump the step programs alone, and every operation
    of the dumped module maps to its name stack."""
    import os
    import subprocess
    import sys

    dump = tmp_path / "hlo"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_dump_to={dump} {E.DUMP_FLAGS}"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    subprocess.run([sys.executable, "-c", _DUMPED], env=env, check=True,
                   timeout=300)
    stacks = E.read_dump(dump)
    assert list(stacks) == ["jit__step_n"]
    scopes = {E.scope_of(s) for s in stacks["jit__step_n"].values()}
    assert {"kv_pool", "mlp"} <= scopes


def test_hlo_stacks_reads_each_instruction_of_each_module():
    text = "\n".join([
        "HloModule jit__step_n, is_scheduled=true",
        "%fused_computation (param_0: f32[4]) -> f32[4] {",
        '  ROOT %copy.3 = f32[4]{0} copy(f32[4]{0} %p), metadata={op_name='
        '"jit(_step_n)/while/body/layers/while/body/closed_call/kv_pool/'
        'transpose" stack_frame_id=4}',
        "}",
        '%fusion.12 = f32[4]{0} fusion(%a), kind=kLoop, calls=%f, metadata='
        '{op_name="jit(_step_n)/while/body/sample/argmax"}, backend_config={}',
        "HloModule jit__prefill_step, is_scheduled=true",
        '  %fusion.12 = f32[4]{0} fusion(%a), metadata={op_name='
        '"jit(_prefill_step)/lm_head/dot_general"}',
        "  %p = f32[4]{0} parameter(0)",
    ])
    stacks = E.hlo_stacks(text)
    assert {m: {i: E.scope_of(s) for i, s in st.items()}
            for m, st in stacks.items()} == {
        "jit__step_n": {"copy.3": "kv_pool", "fusion.12": "sample"},
        "jit__prefill_step": {"fusion.12": "lm_head"}}
