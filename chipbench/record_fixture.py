"""Record the small trace that ``tests/test_trace.py`` reduces.

On a TPU: a tiny ``repro.Aligner`` batch (the wavefront kernel) and a
Pallas batch normalization, three times each inside the benchmark's
window and call spans, with a program span around each call and a
host-side pause between calls, profiled with the python tracer off.

  python3 -m chipbench.record_fixture OUT.xplane.pb
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time

from chipbench import run, trace


def main(out: str) -> None:
    run._prepare_environment(cache=False)
    import jax
    import numpy as np
    import repro
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_fixture: needs a TPU")
    tracer = run._profiled_tracer()
    rng = np.random.default_rng(0)
    ref = np.cumsum(rng.standard_normal(4096)).astype(np.float32)
    q = np.cumsum(rng.standard_normal((16, 256)), 1).astype(np.float32)
    al = repro.Aligner(ref, backend="kernel", tracer=tracer)
    x = jax.numpy.asarray(rng.standard_normal((16, 2048)), np.float32)
    norm = jax.jit(ops.normalize)
    jax.block_until_ready(al(q).cost)
    jax.block_until_ready(norm(x))
    d = tempfile.mkdtemp(prefix="chipbench-fixture-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(run.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation(run.CALL_SPAN):
                jax.block_until_ready(al(q).cost)
                jax.block_until_ready(norm(x))
            with tracer.span("fixture.pause"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    shutil.copy(trace.find_xplane(d), out)
    shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
