"""Readers shared by per-layer metrics of the same kind; each metric's
own file under ``metrics/`` picks one.  A reader gets the run's context
(``ctx.trace``: the trace summary, ``ctx.counters``: the program's
counters over the window, ``ctx.work``: cells and bytes of the window's
sweeps, ``ctx.peaks``: the chip's peaks) and returns a number, or None
where it finds nothing to read."""

from __future__ import annotations

from chipbench import counts


def wavefront_roofline(ctx):
    """The wavefront kernel's share of its roofline, in %: the least
    time the window's real DP cells need (5 VPU ops each at the VPU
    ceiling, or their bytes at the HBM bandwidth, whichever is longer)
    over the kernel's summed device time in the window."""
    kernel_ns = ctx.trace.kernel_ns.get("wavefront", 0)
    if not kernel_ns or not ctx.work["cells"]:
        return None
    least_s, _ = counts.roofline_s(ops=counts.sdtw_ops(ctx.work["cells"]),
                                   bytes_=ctx.work["bytes"], peaks=ctx.peaks)
    return 100.0 * least_s / (kernel_ns / 1e9)


def device_idle_share(ctx):
    """% of the window in which no op ran on the device."""
    return 100.0 * ctx.trace.idle_share
