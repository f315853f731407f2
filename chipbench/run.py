"""Run one cell of ``BENCHMARK.json`` on the TPU of this machine and
print its metrics.

  python3 -m chipbench.run --workload paper_batch.sweep --seed 7 \\
      --seconds 40 --trace 0

From the root of a checkout.  The cell's configuration, traffic and
per-layer readers are found by name (``layout.py``).  The run:

  1. refuses to measure without a TPU, or with fewer chips than the
     cell asks for (exit 2, no result);
  2. builds the data on the device from ``--seed`` and warms up every
     shape the window uses (``setup_s``: process start to the window);
  3. runs a closed loop with one caller: calls back to back, each one
     waited for; the window ends with the first call that ends after
     ``--seconds``, and every rate is all the work of all its calls
     over the window's length;
  4. with ``--trace 1`` the window is profiled and the per-layer
     readers take their numbers from the trace and the counters;
  5. reads the device's peak memory, frees the program's state, and
     checks a sample of the window's answers against ``reference.py``.

The last line on stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``, each number compared beside its limit.  The checks
are also the last lines on stderr.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import types

from chipbench import layout

WINDOW_SPAN, CALL_SPAN = "chipbench.window", "chipbench.call"


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc clock)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def _prepare_environment(cache: bool) -> None:
    """Put the program on the path and fix its caches before JAX loads:
    the compile cache at a fixed path inside the checkout, no tuning."""
    src = layout.CHECKOUT / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"chipbench: the program is not in this checkout "
                 f"({src / 'repro'} is missing)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    os.environ["REPRO_TUNE_CACHE"] = "off"
    if cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
            layout.CHECKOUT / ".jax_cache")


def _profiled_tracer():
    """The program's span tracer, also writing every span into the
    profiler's trace, so idle gaps can be laid beside host work."""
    import jax
    from repro import obs

    class ProfiledTracer(obs.Tracer):
        def __init__(self):
            super().__init__()
            self._annotations = {}

        def _enter(self, span):
            ann = jax.profiler.TraceAnnotation(span.name)
            ann.__enter__()
            self._annotations[id(span)] = ann
            super()._enter(span)

        def _exit(self, span, *, error):
            super()._exit(span, error=error)
            self._annotations.pop(id(span)).__exit__(None, None, None)

    return ProfiledTracer()


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class _CompileCounter:
    """Counts the programs JAX builds or loads while ``active``."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kwargs):
        if self.active and event == self.event:
            self.count += 1


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, overrides: dict | None = None,
             benchmark=None, root=layout.HERE,
             peaks: dict | None = None) -> dict:
    """One run of one cell; returns the result object.  ``overrides``
    ({"config": {...}, "traffic": {...}}), ``require_tpu=False`` and
    ``peaks`` are for the tests, which run tiny cells on the CPU without
    the persistent compile cache."""
    cell = layout.load_cell(workload, benchmark=benchmark, root=root)
    for part, extra in (overrides or {}).items():
        getattr(cell, part).update(extra)
    _prepare_environment(cache=require_tpu)
    import jax
    import numpy as np
    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache
    from chipbench import peaks as peak_table
    from chipbench import trace as tr

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (JAX sees "
                         f"{devices[0].platform!r}); nothing is measured")
    if len(devices) < cell.chips:
        raise SystemExit(f"chipbench: {workload} needs {cell.chips} "
                         f"chips, JAX sees {len(devices)}")
    if require_tpu:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        peaks = peak_table.peaks(devices[0].device_kind)
    compiles = _CompileCounter()

    tracer = _profiled_tracer() if trace else obs.Tracer()
    system = cell.system().System(cell, seed, tracer=tracer)
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=_profile_options())

    setup_s = process_age_s()
    compiles.active = True
    durations = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while True:
            with jax.profiler.TraceAnnotation(CALL_SPAN):
                ts = time.perf_counter()
                system.call(len(durations))
                te = time.perf_counter()
            durations.append(te - ts)
            if te > deadline:
                break
    window_s = te - t0
    compiles.active = False
    if trace:
        jax.profiler.stop_trace()

    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    e2e = system.end_to_end(window_s)
    counters = dict(system.counters(),
                    programs_built_in_window=compiles.count)
    work = system.work()
    attempted = system.attempted()
    system.release()
    gc.collect()

    rng = np.random.default_rng(seed)
    sample = system.sample(rng)
    readings = system.compare(sample, system.program_answers(sample))
    checks = {k: {"value": v, "limit": system.limits[k]}
              for k, v in readings.items()}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": 0}
    if trace:
        events = tr.load(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = tr.summarize(events, tr.window_of(events, WINDOW_SPAN))
        ctx = types.SimpleNamespace(trace=summary, counters=counters,
                                    work=work, peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_ns / 1e9,
                      window_s=summary.window_ns / 1e9)
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_by_host}
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(metrics=metrics, device=device)
    result["window"] = {"calls": len(durations), "seconds": window_s,
                        **counters}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    w = result["window"]
    print(f"window: {w['calls']} calls in {w['seconds']:.3f} s, "
          f"programs built in it: {w['programs_built_in_window']}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
