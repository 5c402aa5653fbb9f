"""``duct_commit_kernel``'s share of the HBM roofline: the bytes a
superstep's commit must move (``roofline.duct_commit_bytes``) over the
peak, over the kernel's summed device time per superstep."""

from roofline import duct_commit_bytes, share


def read(r):
    secs = r.trace.time_of("_commit_kernel")
    runs, _ = r.trace.module("chunk")
    if not secs or not runs or not r.counts["windows"]:
        return None
    c = r.counts
    W = c["superstep_windows"]
    commits = runs * c["windows_per_call"] // W
    pushed = c["pushed"] / c["windows"] * W
    return share(duct_commit_bytes(c["R"], c["L"], pushed), secs / commits,
                 r.peaks["hbm_bytes_per_s"])
