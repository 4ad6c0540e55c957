"""Share of the traced window with no operation on the device (trace)."""
import derive


def read(run):
    return derive.idle_pct(run)
