"""Device milliseconds a window spends in the chunk program outside the
Pallas kernels: the window core's XLA work (gathers, app step, staging,
the snapshot select, barrier release and clock advance)."""

KERNELS = ("_window_kernel", "_commit_kernel")


def read(r):
    runs, secs = r.trace.module("chunk")
    if not runs:
        return None
    kernels = sum(r.trace.time_of(k) for k in KERNELS)
    return 1e3 * (secs - kernels) / (runs * r.counts["windows_per_call"])
