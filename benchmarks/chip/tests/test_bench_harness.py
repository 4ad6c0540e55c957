"""The harness finds everything by name, makes traffic from the seed, and
refuses to run without the chip (CPU)."""
import json
import os
import shutil
import subprocess
import sys

import tiny

import numpy as np
import pytest

import harness
from traffic import Traffic, load_mix, quantiles


def test_real_cells_resolve_to_their_files():
    bench = json.loads((tiny.CHECKOUT / "BENCHMARK.json").read_text())
    assert {w["name"] for w in bench["workloads"]} >= {
        "qwen2.5-3b.long-doc", "qwen2.5-3b.chat"}
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.family.__file__.endswith(
            f"families/{cell.config['family']}.py")
        assert cell.arch.vocab == cell.config["vocab_size"]
        harness.engine_configs(cell.config["engine"])
        assert cell.mix["loop"] == "closed"
        assert 0 < float(cell.params["max_gap_limit"])
        assert cell.per_layer, w["name"]
        names = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", "output_tok_s"} <= names
        for m in cell.per_layer + cell.end_to_end:
            assert callable(harness.load_reader(m["name"]))
            assert m.get("moves", m["name"]) in names | {m["name"]}


def test_a_cell_added_as_files_and_an_entry_resolves(tmp_path):
    root = tiny.make_root(tmp_path, {"new.cell": {"max_gap_limit": 1.0}})
    bench = tiny.bench({"new.cell": ("tiny-dense", "tiny-closed")})
    bench["per_layer"].append({
        "name": "new_metric", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "device",
        "moves": "output_tok_s", "workloads": ["new.cell"]})
    (root / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    cell = harness.load_cell("new.cell", root, bench)
    assert cell.config["arch"] == "qwen2.5-3b-smoke"
    assert cell.mix["clients"] == 3 and cell.params["max_gap_limit"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert harness.load_reader("new_metric", root)(None) == 42.0
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell", root, bench)


@pytest.mark.parametrize("mix", ["long-doc", "chat"])
def test_traffic_is_a_function_of_the_seed(mix):
    m = load_mix(mix)
    a = Traffic(m, 1000, 2 ** 31 + 17)
    b = Traffic(m, 1000, 2 ** 31 + 17)
    c = Traffic(m, 1000, 5)
    for i in (0, 5, 130):
        ra, rb = a.request(i), b.request(i)
        assert np.array_equal(ra.prompt, rb.prompt)
        assert ra.max_new == rb.max_new
    assert any(not np.array_equal(a.request(i).prompt, c.request(i).prompt)
               for i in range(4))
    # every seed serves the same multiset of length pairs in each block,
    # in another order unless the mix counts its requests
    blk = m["block"]
    for start in (0, blk):
        block = range(start, start + blk)
        assert sorted(a.lengths(i) for i in block) == \
            sorted(c.lengths(i) for i in block)
    same = [a.lengths(i) for i in range(2 * blk)] == \
        [c.lengths(i) for i in range(2 * blk)]
    assert same == ("requests" in m)


@pytest.mark.parametrize("mix", ["long-doc"])
def test_traffic_hits_its_length_distribution(mix):
    m = load_mix(mix)
    t = Traffic(m, 1000, 3)
    plens = np.array([t.lengths(i)[0] for i in range(4 * m["block"])])
    outs = np.array([t.lengths(i)[1] for i in range(4 * m["block"])])
    for xs, d in ((plens, m["prompt"]), (outs, m["output"])):
        assert xs.min() >= d["lo"] and xs.max() <= d["hi"]
        assert abs(xs.mean() - (d["lo"] + d["hi"]) / 2) < 0.02 * d["hi"]
        # uniform: each quarter of the range holds a quarter of the lengths
        edges = np.linspace(d["lo"], d["hi"] + 1, 5)
        assert np.histogram(xs, edges)[0].tolist() == [len(xs) // 4] * 4


def test_an_open_loop_mix_is_refused(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "open.json").write_text('{"loop": "open"}')
    with pytest.raises(ValueError):
        load_mix("open", tmp_path)


def test_uniform_quantiles_cover_every_length_equally():
    q = quantiles({"dist": "uniform", "lo": 10, "hi": 13}, 8)
    assert sorted(q.tolist()) == [10, 10, 11, 11, 12, 12, 13, 13]


@pytest.mark.parametrize("median,sigma", [(256, 0.9), (64, 0.7), (12, 0.6),
                                          (1020, 0.5), (129, 1.0)])
@pytest.mark.parametrize("n", [16, 101])
def test_lognormal_quantiles_have_the_stated_median(median, sigma, n):
    wide = {"dist": "lognormal", "median": median, "sigma": sigma,
            "lo": 1, "hi": 10 ** 6}
    q = quantiles(wide, n)
    assert abs(np.median(q) - median) <= 1
    assert np.all(np.diff(q) >= 0)
    # the log of the lengths spreads by sigma: the quartiles at exp(+-0.674 s)
    lo_q, hi_q = np.quantile(np.log(q), [0.25, 0.75])
    assert hi_q - lo_q == pytest.approx(2 * 0.6745 * sigma, rel=0.15)


def test_lognormal_quantiles_are_clipped_to_lo_and_hi():
    prompt = {"dist": "lognormal", "median": 256, "sigma": 0.9,
              "lo": 32, "hi": 1024}
    output = {"dist": "lognormal", "median": 64, "sigma": 0.7,
              "lo": 8, "hi": 256}
    for d in (prompt, output):
        q = quantiles(d, 16)
        assert q.min() >= d["lo"] and q.max() <= d["hi"]
        raw = quantiles({**d, "lo": 1, "hi": 10 ** 6}, 16)
        assert np.array_equal(q, np.clip(raw, d["lo"], d["hi"]))
    # the longest prompts of a block reach the clip
    assert quantiles(prompt, 16).max() == prompt["hi"]


@pytest.mark.parametrize("mix", ["long-doc", "chat"])
def test_every_request_of_a_mix_fits_its_rows(mix):
    m = load_mix(mix)
    t = Traffic(m, 1000, 11)
    pairs = [t.lengths(i) for i in range(m["block"])]
    assert all(p + o <= m["max_len"] for p, o in pairs)
    assert m["prompt"]["hi"] + m["output"]["hi"] <= m["max_len"]
    assert m["clients"] <= m["rows"]


def test_a_mix_with_requests_states_whole_blocks(tmp_path):
    (tmp_path / "traffic").mkdir()
    base = {"loop": "closed", "block": 4}
    (tmp_path / "traffic" / "ok.json").write_text(
        json.dumps({**base, "requests": 8}))
    (tmp_path / "traffic" / "part.json").write_text(
        json.dumps({**base, "requests": 6}))
    assert load_mix("ok", tmp_path)["requests"] == 8
    with pytest.raises(ValueError, match="whole number of blocks"):
        load_mix("part", tmp_path)


def test_an_unknown_family_stops_with_its_file_named(tmp_path):
    root = tiny.make_root(tmp_path, {"odd.cell": {"max_gap_limit": 1.0}})
    cfg = {**tiny.CONFIGS["tiny-dense"], "family": "no-such-family"}
    (root / "configs" / "odd.json").write_text(json.dumps(cfg))
    bench = tiny.bench({"odd.cell": ("odd", "tiny-closed")})
    with pytest.raises(FileNotFoundError, match=r"families/no-such-family\.py"):
        harness.load_cell("odd.cell", root, bench)


def test_an_unknown_engine_key_stops_with_the_key_named():
    e = {**tiny.CONFIGS["tiny-dense"]["engine"], "pages_per_row": 4}
    with pytest.raises(ValueError, match="'pages_per_row'"):
        harness.engine_configs(e)


def test_engine_keys_set_the_fields_of_their_name():
    from repro.serving import CacheConfig, EngineConfig
    cfg = json.loads((tiny.CHIP / "configs" / "qwen2.5-3b.json").read_text())
    cache, config = harness.engine_configs(cfg["engine"])
    # what the configuration's five keys set, and nothing else
    assert cache == CacheConfig(layout="paged", page_size=16, kv_dtype="f32")
    assert config == EngineConfig(prefill_chunk=128, steps_per_sync=8)
    cache, config = harness.engine_configs(
        {"kv_layout": "paged", "n_pages": 40, "host_spill": False,
         "snapshots": True, "prefill_budget": 2, "note": "prose"})
    assert (cache.n_pages, cache.host_spill, cache.snapshots) == \
        (40, False, True)
    assert config.prefill_budget == 2


def test_run_refuses_without_a_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(tiny.CHIP / "run.py"), "--workload",
         "qwen2.5-3b.long-doc", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=tiny.CHECKOUT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs" in p.stderr


def test_run_refuses_outside_a_checkout(tmp_path):
    """With only ``BENCHMARK.json`` and the benchmark's own files, and no
    program under test, a run exits non-zero and prints no result."""
    shutil.copy(tiny.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen2.5-3b.long-doc", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _finished_run(seed, max_news):
    run = harness.Run(None, seed, {})
    for i, n in enumerate(max_news):
        rec = harness.Rec(i, 100 + i, n, np.zeros(100 + i, np.int32))
        rec.out = np.zeros(n, np.int32)
        run.recs.append(rec)
    return run


def test_check_sample_starts_with_the_longest_and_reaches_its_target():
    outs = [64 + 12 * i for i in range(16)]
    picked = harness.sample(_finished_run(7, outs))
    assert picked[0].index == 15
    served = [r.max_new for r in picked]
    assert sum(served) >= harness.SERVED_CHECKED
    assert sum(served[:-1]) < harness.SERVED_CHECKED
    assert len({r.index for r in picked}) == len(picked)


def test_check_sample_is_drawn_from_the_seed():
    outs = [64 + 12 * i for i in range(16)]
    a = [r.index for r in harness.sample(_finished_run(7, outs))]
    b = [r.index for r in harness.sample(_finished_run(7, outs))]
    c = [r.index for r in harness.sample(_finished_run(2 ** 31 + 9, outs))]
    assert a == b and a != c
