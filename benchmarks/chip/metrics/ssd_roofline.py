"""SSD scan kernel, decode and prefill chunk: least time for the SSD work
of the window's requests, from the traffic, over their device time."""
import derive


def read(run):
    w = derive.window_work(run)
    return None if w is None else derive.roofline_pct(
        run, w["ssd"], ("repro_ssd_decode", "repro_ssd_prefill_chunk"))
