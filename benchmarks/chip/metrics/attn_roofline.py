"""Paged attention kernels (decode and prefill chunk): least time for
the attention work of the window's requests, from the traffic, over their
device time."""
import derive


def read(run):
    w = derive.window_work(run)
    return None if w is None else derive.roofline_pct(
        run, w["attention"], derive.ATTENTION_KERNELS)
