"""Model operations of the window's requests (from the traffic) over the
device time of the decode and prefill programs times the bf16 peak."""
import derive


def read(run):
    return derive.step_mfu_pct(run)
