"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load(path)`` reads an ``.xplane.pb`` with nothing but JAX and keeps:

  * device ops: the "XLA Ops" line of every ``/device:TPU:<n>`` plane,
    each op as (name, start, duration, whether it is a Pallas kernel);
  * host spans: the benchmark's and the program's named spans
    (``chipbench.call``, ``aligner.dispatch``, ...), which
    ``jax.profiler.TraceAnnotation`` writes on the host's timeline.

``summarize`` then takes, inside the traced window:

  * busy time per device as the union of its op intervals, averaged
    over the devices;
  * every idle gap between busy intervals, attributed to the innermost
    host span that covers the gap's midpoint;
  * the summed device time of each kernel, by ``KERNELS`` below.

Times are nanoseconds on the profiler's clock.  Host and device events
share it to within about half a millisecond.
"""

from __future__ import annotations

import dataclasses
import glob
import re

# A Pallas kernel appears as a ``tpu_custom_call`` op named after the
# jitted wrapper that calls it.  The wavefront kernel is dispatched by
# ``kernels/ops.py:_dispatch``; a stable ``name=`` on its pallas_call
# should contain "wavefront".  The batch normalizer is
# ``_normalize_padded``; it is never the wavefront.
KERNELS = {
    "wavefront": re.compile(r"^(_dispatch|.*wavefront)", re.I),
    "normalizer": re.compile(r"normaliz", re.I),
}
# host spans: dotted lower-case names; the python tracer's "$file:line"
# events and the runtime's own are left out
_SPAN = re.compile(r"^[a-z_][a-z0-9_]*(\.[a-z0-9_]+)+$")
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def _op(name: str) -> tuple[str, bool]:
    """(op name, is a Pallas kernel) from an "XLA Ops" event name, which
    is the op's HLO text: ``%_dispatch.1 = (...) custom-call(...)``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return head, 'custom_call_target="tpu_custom_call"' in name


def load(path) -> dict:
    """{"devices": {plane: [[op, kernel, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]} from an .xplane.pb file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    op, kernel = _op(e.name)
                    ops.append([op, kernel, int(e.start_ns),
                                int(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if _SPAN.match(e.name):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"devices": devices, "host": host}


def find_xplane(directory) -> str:
    paths = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{directory}, found {len(paths)}")
    return paths[0]


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """The complement of merged ``busy`` intervals within [lo, hi)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def host_activity(host, t: int) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no host span"


@dataclasses.dataclass
class Summary:
    window_ns: int                 # traced time
    busy_ns: float                 # mean over devices
    kernel_ns: dict                # kernel -> summed device ns
    top_ops: list                  # [[op, seconds], ...] by total time
    idle_by_host: list             # [[host span, seconds], ...]
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def _ranked(pairs, top: int) -> list:
    total: dict[str, float] = {}
    for k, v in pairs:
        total[k] = total.get(k, 0) + v
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:top]]


def kernel_of(op: str, is_kernel: bool) -> str | None:
    """Which of ``KERNELS`` a Pallas op is (normalizer checked first)."""
    if not is_kernel:
        return None
    if KERNELS["normalizer"].search(op):
        return "normalizer"
    if KERNELS["wavefront"].search(op):
        return "wavefront"
    return None


def _family(op: str) -> str:
    """An op's name without its HLO instance number: fusion.12 -> fusion."""
    return re.sub(r"\.\d+$", "", op)


def summarize(trace: dict, window: tuple[int, int], *,
              top: int = 10) -> Summary:
    """Busy, idle and kernel time inside ``window`` (start, end) ns.
    A trace with no TPU plane is refused."""
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty window {window}")
    if not trace["devices"]:
        raise ValueError("the trace holds no TPU device plane")
    busy_total = 0
    kernel_ns: dict[str, float] = {}
    op_ns: dict[str, float] = {}
    idle: dict[str, float] = {}
    for ops in trace["devices"].values():
        inside = [o for o in ops if o[2] < hi and o[2] + o[3] > lo]
        busy = union(((s, s + d) for _, _, s, d in inside), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for op, is_kernel, s, d in inside:
            d = min(s + d, hi) - max(s, lo)
            k = kernel_of(op, is_kernel)
            if k is not None:
                kernel_ns[k] = kernel_ns.get(k, 0) + d
            fam = _family(op)
            op_ns[fam] = op_ns.get(fam, 0) + d
        for s, e in gaps(busy, lo, hi):
            who = host_activity(trace["host"], (s + e) // 2)
            idle[who] = idle.get(who, 0) + (e - s)
    n = len(trace["devices"])

    def ranked(d):
        return _ranked(((k, v / n / 1e9) for k, v in d.items()), top)

    return Summary(window_ns=hi - lo, busy_ns=busy_total / n,
                   kernel_ns={k: v / n for k, v in kernel_ns.items()},
                   top_ops=ranked(op_ns), idle_by_host=ranked(idle),
                   devices=n)


def window_of(trace: dict, span: str) -> tuple[int, int]:
    """(start, end) of the one host span named ``span``."""
    found = [(s, s + d) for name, s, d in trace["host"] if name == span]
    if len(found) != 1:
        raise ValueError(f"expected one {span!r} span in the trace, "
                         f"found {len(found)}")
    return found[0]
