"""Whole-window share of the HBM roofline: the bytes the semantics need a
window to move (``roofline.window_bytes``) over the peak bandwidth, over
the chunk program's device time per window."""

from roofline import share, window_bytes


def read(r):
    runs, secs = r.trace.module("chunk")
    if not runs or not r.counts["windows"]:
        return None
    c, w = r.counts, r.counts["windows"]
    per_window = window_bytes(c["n"], c["R"], c["L"], c["simels"],
                              c["n_colors"], c["drained"] / w,
                              c["pushed"] / w)
    return share(per_window, secs / (runs * c["windows_per_call"]),
                 r.peaks["hbm_bytes_per_s"])
