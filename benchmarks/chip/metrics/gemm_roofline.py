"""GEMM kernel: least time for the projection work of the window's requests,
from the traffic, over its device time."""
import derive


def read(run):
    w = derive.window_work(run)
    return None if w is None else derive.roofline_pct(
        run, [w["gemm"]], ("repro_gemm",))
