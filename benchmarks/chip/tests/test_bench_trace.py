"""The trace reduction and the work functions, on known inputs (CPU)."""
import tiny  # noqa: F401  (puts the benchmark on sys.path)

import pytest

import trace_reduce as T
import work as W
from arch import arch_of, load_config


def _trace():
    # device ops in ns: two overlapping gemms, an attention op, a gap
    ops = [("repro_gemm", 100, 300), ("repro_gemm", 250, 400),
           ("while", 90, 420),
           ("repro_flash_decode_paged", 600, 900), ("copy", 950, 1000)]
    mods = [("jit__step_n", 100, 400), ("jit__prefill_step", 600, 1000)]
    host = [("bench.step", 0, 500), ("bench.wait", 500, 600),
            ("bench.step", 600, 1200)]
    return T.Trace(ops={"/device:TPU:0": ops},
                   modules={"/device:TPU:0": mods}, host=host,
                   window=(0, 1200))


def test_busy_is_the_union_of_operations():
    tr = _trace()
    # [90, 420] + [600, 900] + [950, 1000] = 330 + 300 + 50 ns
    assert T.busy_s(tr) == pytest.approx(680e-9)
    assert T.window_s(tr) == pytest.approx(1200e-9)


def test_operation_names_drop_the_instance_number():
    text = ("%repro_gemm.79 = bf16[16,256]{1,0} custom-call(bf16[16,2048] "
            "%repro_rmsnorm.28, bf16[2048,256] %fusion.20)")
    assert T.op_name(text) == "repro_gemm"
    assert T.op_name("%while = (s32[]) while(...)") == "while"


def test_kernel_time_sums_its_operations():
    tr = _trace()
    assert T.kernel_s(tr, "repro_gemm") == pytest.approx(350e-9)
    assert T.kernel_s(tr, "repro_flash_decode_paged") == pytest.approx(300e-9)
    assert T.kernel_s(tr, "repro_ssd_scan") == 0.0


def test_program_time_and_window_clip():
    tr = _trace()
    assert T.program_s(tr, "_step_n") == pytest.approx(300e-9)
    tr.window = (0, 800)
    assert T.program_s(tr, "_prefill_step") == pytest.approx(200e-9)
    assert T.busy_s(tr) == pytest.approx(530e-9)


def test_idle_gaps_are_named_by_the_covering_span():
    gaps = T.idle_gaps(_trace())
    # [420, 600]: step 80 ns, wait 100 ns; [1000, 1200]: step;
    # [0, 90]: step; [900, 950]: step
    assert gaps[0] == ["bench.step", pytest.approx(200e-9)]
    assert gaps[1] == ["bench.wait", pytest.approx(180e-9)]
    assert sum(g[1] for g in gaps) == pytest.approx(520e-9)


def test_top_ops_leave_out_control_flow():
    top = dict(T.top_ops(_trace()))
    assert top["repro_gemm"] == pytest.approx(350e-9)
    assert "while" not in top


def test_union_merges_touching_intervals():
    assert T.union([(5, 7), (1, 3), (3, 4)]) == [[1, 4], [5, 7]]


@pytest.fixture(scope="module")
def qwen():
    return arch_of(load_config("qwen2.5-3b"))


def test_matmul_flops_per_token_from_param_count(qwen):
    from repro.configs.registry import get_arch
    cfg = get_arch("qwen2.5-3b")
    # the program's analytic count less the embedding and the norms
    want = cfg.param_count() - cfg.vocab_size * cfg.d_model \
        - cfg.n_layers * 2 * cfg.d_model
    assert W.matmul_params(qwen) == want
    assert W.gemm(qwen, 1, 0, 0).flops == 2 * want


def test_live_kv_bytes(qwen):
    # 36 layers x K and V x 2 heads x 128 x bf16
    assert W.kv_bytes_per_token(qwen) == 36_864
    one = W.attention(qwen, 2999, 3000, chunk=1)   # one token at context 3000
    assert one.bytes == 3000 * 36_864
    assert one.flops == 4 * 3000 * 16 * 128 * 36
    chunked = W.attention(qwen, 0, 256, chunk=128)
    assert chunked.bytes == (128 + 256) * 36_864
    assert chunked.flops == 4 * (256 * 257 // 2) * 16 * 128 * 36


def test_roofline_bound_names_the_limit(qwen):
    t, bound = W.attention(qwen, 2999, 3000, chunk=1).bound_s(197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(3000 * 36_864 / 819e9)


def test_window_work_is_the_requests_whole_work(qwen):
    import derive
    from types import SimpleNamespace as NS
    recs = [NS(plen=300, max_new=5, out=[0] * 5),
            NS(plen=128, max_new=1, out=[0])]
    cell = NS(arch=qwen, config={"engine": {"prefill_chunk": 128}})
    run = NS(trace=T.Trace(), recs=recs, cell=cell,
             cycles=[NS(prefill_steps=3, decode_steps=8)],
             done=lambda: recs)
    w = derive.window_work(run)
    pre, dec = w["attention"]
    # prompts in pieces of 128: 300 -> 128, 256, 300; 128 -> 128
    assert pre.bytes == (128 + 256 + 300 + 128) * 36_864
    # generated tokens fed one at a time: positions 300..303
    assert dec.bytes == (301 + 302 + 303 + 304) * 36_864
    fed = 304 + 128
    assert w["gemm"].flops == 2 * (fed * W.matmul_params(qwen)
                                   + 6 * W.head_params(qwen))
    assert w["model_flops"] == w["gemm"].flops + 4 * (
        W.ctx_sum(0, 304) + W.ctx_sum(0, 128)) * 16 * 128 * 36
    # a request that never completed leaves the work unknown
    run.done = lambda: recs[:1]
    assert derive.window_work(run) is None
