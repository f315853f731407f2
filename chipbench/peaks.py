"""Peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not here is an error, not a default.

``vpu_ops_per_s``: float32 elementwise VPU operations per second, the
ceiling of a min-plus recurrence, which never uses the MXU.  No such
peak is published for v5e, so it is measured by ``vpu_ceiling.py``.
``hbm_bytes_per_s``: the published HBM bandwidth.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "vpu_ops_per_s": 5.683116372581876e12,
        "vpu_source": "measured by chipbench/vpu_ceiling.py on one TPU v5e, "
                      "16 chains, best of 5 calls of 0.369 s",
        "hbm_bytes_per_s": 819e9,
        "hbm_source": "Google Cloud documentation, TPU v5e: 819 GB/s",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises UnknownDevice otherwise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}: known "
            f"{sorted(PEAKS)}; measure its VPU ceiling with "
            f"chipbench/vpu_ceiling.py and add it to chipbench/peaks.py"
        ) from None
