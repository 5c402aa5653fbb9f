"""``duct_window_kernel``'s share of the HBM roofline: the bytes a window's
ring pass must move (``roofline.duct_window_bytes``) over the peak, over
the kernel's summed device time per window."""

from roofline import duct_window_bytes, share


def read(r):
    secs = r.trace.time_of("_window_kernel")
    runs, _ = r.trace.module("chunk")
    if not secs or not runs or not r.counts["windows"]:
        return None
    c, w = r.counts, r.counts["windows"]
    per_window = duct_window_bytes(c["n"], c["R"], c["L"], c["drained"] / w,
                                   c["pushed"] / w)
    return share(per_window, secs / (runs * c["windows_per_call"]),
                 r.peaks["hbm_bytes_per_s"])
