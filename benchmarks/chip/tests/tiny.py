"""A tiny benchmark root for the CPU tests: the program's smoke-sized
Qwen2 under a small closed-loop mix, with the real metric readers.
Nothing here runs on the chip."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
CHECKOUT = CHIP.parents[1]
for p in (str(CHIP), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

ENGINE = {"page_size": 4, "kv_dtype": "f32", "prefill_chunk": 8,
          "steps_per_sync": 4}
CONFIGS = {
    "tiny-dense": {
        "source": "smoke-sized Qwen2", "arch": "qwen2.5-3b-smoke",
        "family": "dense", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 1, "head_dim": 16, "vocab_size": 128,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
        "torch_dtype": "float32", "reduced": [],
        "engine": {"kv_layout": "paged", **ENGINE}},
}
MIXES = {
    "tiny-closed": {"loop": "closed", "clients": 3, "rows": 3, "max_len": 48,
                    "prompt": {"dist": "uniform", "lo": 9, "hi": 30},
                    "output": {"dist": "uniform", "lo": 3, "hi": 12},
                    "block": 8},
}
PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def bench(cells: dict) -> dict:
    """A BENCHMARK.json body whose workloads are ``{name: (config, mix)}``,
    with the real metric list."""
    real = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    return {**real, "workloads": [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "test"}
        for n, (c, t) in cells.items()]}


def make_root(tmp: Path, limits: dict) -> Path:
    """A benchmark directory under ``tmp`` with the tiny files, the real
    readers and ``cells/<name>.json`` from ``limits``."""
    root = tmp / "chip"
    for sub in ("configs", "traffic", "cells"):
        (root / sub).mkdir(parents=True)
    shutil.copytree(CHIP / "metrics", root / "metrics")
    for name, cfg in CONFIGS.items():
        (root / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, mix in MIXES.items():
        (root / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for name, params in limits.items():
        (root / "cells" / f"{name}.json").write_text(json.dumps(params))
    return root
