"""The engine's profiler spans, slot-use counters and device name scopes
(CPU): spans nest under ``engine.step`` in a real trace, the counters
take their exact values on a known schedule without a device read, and
the compiled step programs carry the scopes in their op metadata."""
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.audit import jit_cache_audit, no_transfer_audit
from repro.configs.registry import get_arch
from repro.models.model import build_model
from repro.serving import CacheConfig, EngineConfig, ServingEngine
from repro.serving.engine import SPANS

COUNTERS = ("prefill_slots", "prefill_positions", "decode_row_steps",
            "decode_positions")


@pytest.fixture(scope="module")
def model():
    m = build_model(get_arch("qwen2.5-3b-smoke"))
    return m, m.init_params(jax.random.PRNGKey(0))


def _engine(model, prefill_chunk=8):
    m, params = model
    return ServingEngine(
        m, params, batch=2, max_len=32,
        cache=CacheConfig(layout="paged", page_size=4),
        config=EngineConfig(prefill_chunk=prefill_chunk, steps_per_sync=4))


def _submit_two(eng):
    eng.submit(list(range(1, 11)), 6)     # prompt 10, 6 new: total 16
    eng.submit([3, 4, 5, 6, 7], 3)        # prompt 5, 3 new: total 8


# Schedules by hand, two rows, steps_per_sync 4.
# prefill_chunk 8: prefill step 1 feeds 8 + 5 positions, step 2 feeds 2
# (row 0 ends its prompt) + 1 (row 1 rides along): 2 steps x 2 x 8 slots,
# 16 positions.  Decode call 1 advances row 0 by 4 (10 -> 14) and row 1
# by 1 (6 -> 7 = total - 1); call 2 advances row 0 by 1 (14 -> 15): 2
# calls x 2 x 4 row-steps, 6 positions.
# prefill_chunk 1: no prefill program; the 9 + 4 positions fed before
# the rows finish run in 4 decode calls (row 0 needs 15 positions).
@pytest.mark.parametrize("chunk, want", [
    (8, {"prefill_slots": 32, "prefill_positions": 16,
         "decode_row_steps": 16, "decode_positions": 6}),
    (1, {"prefill_slots": 0, "prefill_positions": 0,
         "decode_row_steps": 32, "decode_positions": 22}),
])
def test_slot_counters_on_a_known_schedule(model, chunk, want):
    eng = _engine(model, chunk)
    _submit_two(eng)
    with jit_cache_audit(eng), no_transfer_audit():
        outs = eng.run()
    assert sorted(len(o) for o in outs.values()) == [3, 6]
    st = eng.stats()
    assert {k: st[k] for k in COUNTERS} == want
    # every position the rows fed is counted once, in one program or the
    # other: prompt plus generated tokens, less the last of each request
    assert st["prefill_positions"] + st["decode_positions"] == (
        (10 + 6 - 1) + (5 + 3 - 1))
    eng.reset_stats()
    assert all(eng.stats()[k] == 0 for k in COUNTERS)


def test_step_spans_nest_under_the_cycle(model, tmp_path):
    eng = _engine(model)
    _submit_two(eng)
    eng.step()                      # compile outside the trace
    eng.submit([9, 8, 7, 6, 5, 4, 3, 2, 1, 2, 3], 2)
    jax.profiler.start_trace(str(tmp_path))
    while eng.queue or eng.stats()["generated_tokens"] < 11:
        eng.step()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    spans = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name in SPANS]
    assert {s[0] for s in spans} == set(SPANS)
    cycles = [s for s in spans if s[0] == "engine.step"]
    for name, t0, t1, _ in spans:
        assert any(c[1] <= t0 and t1 <= c[2] for c in cycles), name
    # span arguments: the cycle number, and the request admitted mid-run
    assert sorted(c[3]["cycle"] for c in cycles) == list(
        range(2, 2 + len(cycles)))
    # (the trace reads a lone id back as a number)
    admitted = [str(s[3]["admitted"]) for s in spans
                if s[0] == "engine.refill" and "admitted" in s[3]]
    assert admitted == ["2"]


@pytest.mark.parametrize("program", ["_step_n", "_prefill"])
def test_step_programs_carry_the_layer_scopes(model, program):
    from repro.core.policy import use_backend

    eng = _engine(model)
    args = (eng.params, eng._mstate, eng._slots)
    if program == "_step_n":
        args += (jnp.ones((eng.batch,), bool),)
    with use_backend("pallas"):
        text = getattr(eng, program).lower(*args).as_text(debug_info=True)
    # op locations carry the name stack, relative inside nested bodies
    stacks = [n.split("/") for n in re.findall(r'loc\("([^"]+)"', text)]
    for scope in ("kv_pool", "attention", "mlp", "lm_head", "sample",
                  "embed", "layers"):
        assert any(scope in st for st in stacks), scope
    # the whole-pool relayout before the paged kernel is pool work
    assert any(st[-2:] == ["kv_pool", "transpose"] for st in stacks)
