"""The dense family reads as the benchmark did before its code moved into
``families/dense.py`` (CPU).

``golden_dense.json`` was recorded with the benchmark's code as it stood
before the move: at the tiny dense configuration on one seed, the
checksum of every weight leaf, the reference logits (in float32 and under
both controls), the work of sixteen requests of the tiny mix and of the
long-doc mix at qwen2.5-3b's sizes, and the per-layer readings of those
requests on the synthetic trace.  Each must be equal to the last bit."""
import hashlib
import json
from pathlib import Path

import tiny

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import weights
from arch import load_config, load_family
from test_bench_trace import _trace
from traffic import Traffic, load_mix

GOLDEN = json.loads((Path(__file__).parent / "golden_dense.json").read_text())
SEED = GOLDEN["seed"]
READERS = ["attn_roofline", "gemm_roofline", "step_mfu.long",
           "decode_step_ms.long", "device_idle_pct.long"]
D = load_family("dense")


def _sha(x):
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(x)).tobytes()).hexdigest()


@pytest.fixture(scope="module")
def tiny_arch():
    return D.arch_of(tiny.CONFIGS["tiny-dense"])


@pytest.fixture(scope="module")
def params(tiny_arch):
    return weights.make(D.shapes, tiny_arch, SEED)


def test_weights_are_bitwise_the_recorded(params):
    got = {p: [list(x.shape), str(x.dtype), _sha(x)]
           for p, x in sorted(weights.flat(params).items())}
    assert got == GOLDEN["weights"]


@pytest.mark.parametrize("quantize", ["", "bf16", "fp8"])
def test_reference_logits_are_bitwise_the_recorded(tiny_arch, params,
                                                   quantize):
    rng = np.random.default_rng([SEED, 9])
    tokens = jnp.asarray(rng.integers(0, tiny_arch.vocab, 48, dtype=np.int32))
    positions = jnp.asarray(np.arange(20, 32, dtype=np.int32))
    lg = np.asarray(D.logits(tiny_arch, params, tokens, positions, quantize))
    digest, head = GOLDEN["logits"][quantize]
    assert [float(v) for v in lg[0, :4]] == head
    assert _sha(lg) == digest


def _run(arch, mix, vocab, chunk):
    t = Traffic(mix, vocab, SEED)
    cell = harness.Cell(tiny.CHIP, "golden", 1, "golden",
                        {"engine": {"prefill_chunk": chunk}}, D, arch,
                        "golden", mix, {}, [], [])
    run = harness.Run(cell, SEED,
                      json.loads((tiny.CHIP / "peaks.json").read_text()))
    for i in range(16):
        plen, out = t.lengths(i)
        rec = harness.Rec(i, plen, out, np.zeros(plen, np.int32))
        rec.out = np.zeros(out, np.int32)
        run.recs.append(rec)
    run.cycles = [harness.Cycle(i % 3, 8) for i in range(12)]
    run.trace = _trace()
    run.notes["device_kind"] = "TPU v5 lite"
    return run


@pytest.mark.parametrize("config", ["tiny-dense", "qwen2.5-3b"])
def test_work_and_readings_are_the_recorded(config, tiny_arch):
    import derive
    if config == "tiny-dense":
        run = _run(tiny_arch, tiny.MIXES["tiny-closed"], 128, 8)
    else:
        run = _run(D.arch_of(load_config("qwen2.5-3b")), load_mix("long-doc"),
                   151936, 128)
    want = GOLDEN["cells"][config]
    w = derive.window_work(run)
    (pre, dec), g = w["attention"], w["gemm"]
    assert {"attention_prefill": [pre.flops, pre.bytes],
            "attention_decode": [dec.flops, dec.bytes],
            "gemm": [g.flops, g.bytes],
            "model_flops": w["model_flops"]} == want["work"]
    assert {m: harness.load_reader(m)(run) for m in READERS} == \
        want["readings"]
    assert run.notes["bounds"] == want["bounds"]
