"""The multivariate wavefront kernel's share of its roofline, in %.

The least time of the window's real DP cells over the wavefront
kernel's summed device time in the window.  A cell of D features needs,
at least, whichever is longest of:

  * the recurrence's 3 VPU operations (two minimums and an add) at the
    measured VPU ceiling;
  * its cost's D-long dot product, 2D flops, at the MXU's published
    bfloat16 peak, which no float32 cost beats on either unit;
  * the bytes of queries, archive and answers at the HBM bandwidth.

Cells and D x cells are the program's counters over the window
(``kernel.wavefront.cells_real`` and ``.feature_cells``); the bytes are
the system's.  None where a counter, the kernel's time or the MXU peak
is missing.
"""

OPS_PER_CELL = 3
FLOPS_PER_FEATURE = 2


def read(ctx):
    import jax
    from chipbench import mxu_peaks
    kernel_ns = ctx.trace.kernel_ns.get("wavefront", 0)
    cells = ctx.counters.get("kernel.wavefront.cells_real", 0)
    feature_cells = ctx.counters.get("kernel.wavefront.feature_cells", 0)
    mxu = mxu_peaks.mxu_bf16_flops_per_s(
        ctx.peaks, jax.devices()[0].device_kind)
    if not kernel_ns or not cells or not feature_cells or not mxu:
        return None
    least_s = max(OPS_PER_CELL * cells / ctx.peaks["vpu_ops_per_s"],
                  FLOPS_PER_FEATURE * feature_cells / mxu,
                  ctx.work["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_ns / 1e9)
