"""The MXU's published bfloat16 peak of the chips the benchmark runs on,
keyed by JAX's ``device_kind``.

A roofline of a cell whose cost could be computed on the MXU (the dot
products of a multivariate cost, -2 q.r plus the norms) bounds that
part of a cell's work by this peak: no float32 cost runs faster, so
the share stays under 100% whichever unit computes it.
"""

from __future__ import annotations

MXU_PEAKS = {
    "TPU v5 lite": {
        "mxu_bf16_flops_per_s": 197e12,
        "mxu_source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s "
                      "bfloat16",
    },
}


def mxu_bf16_flops_per_s(peaks: dict, device_kind: str | None = None):
    """The MXU bfloat16 peak in FLOP/s: from ``peaks`` where it holds
    one, else from the table by ``device_kind``; None where neither
    knows it."""
    if "mxu_bf16_flops_per_s" in peaks:
        return peaks["mxu_bf16_flops_per_s"]
    entry = MXU_PEAKS.get(device_kind)
    return None if entry is None else entry["mxu_bf16_flops_per_s"]
