"""repro — subsequence dynamic time warping (sDTW) on JAX and TPU.

A reproduction of "Optimizing sDTW for AMD GPUs" (cs.DC 2024): batched
sDTW of many query series against a long reference, run by an
anti-diagonal wavefront kernel in Pallas, with an XLA engine, a scan
reference and a float64 oracle to check it against.

One front door::

    import repro
    res = repro.sdtw(queries, reference,
                     outputs=("cost", "start", "end"))   # SDTWResult
    aligner = repro.Aligner(reference, band=128)         # session form
    res = aligner(queries)                               # warm: dispatch

Exports are lazy so ``import repro`` stays free of JAX/Pallas imports
until an entry point is actually touched.
"""

__version__ = "1.1.0"

__all__ = ["sdtw", "Aligner", "SDTWResult",
           "DPSpec", "ALL_OUTPUTS", "tune", "dp"]

_LAZY = {
    "sdtw": ("repro.core.api", "sdtw"),
    "Aligner": ("repro.core.session", "Aligner"),
    "SDTWResult": ("repro.core.result", "SDTWResult"),
    "ALL_OUTPUTS": ("repro.core.result", "ALL_OUTPUTS"),
    "DPSpec": ("repro.core.spec", "DPSpec"),
    "tune": ("repro.tune", None),    # the autotuner subpackage itself
    "dp": ("repro.dp", None),        # the recurrence-algebra subpackage
}


def __getattr__(name):
    try:
        module, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    import importlib
    mod = importlib.import_module(module)
    value = mod if attr is None else getattr(mod, attr)
    globals()[name] = value          # cache: resolve each name once
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
