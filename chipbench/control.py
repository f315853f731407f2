"""The lower-precision control of a cell's check, on the chip.

For each seed: build the cell as a run does, drive a short window of
the timed call, then compare the same sample of answers twice against
the float32 reference: the program's answers, and the reference's own
answers computed in bfloat16 (the precision below the configuration's
float32).  A sound check passes the first and fails the second.

  python3 -m chipbench.control --workload paper_batch.sweep \\
      --seconds 10 --seeds 11 12 13

Prints one JSON line per seed: {"seed", "program": {...}, "control":
{...}, "limits": {...}}.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

from chipbench import layout, run


def readings(workload: str, seed: int, seconds: float) -> dict:
    cell = layout.load_cell(workload)
    from repro import obs
    system = cell.system().System(cell, seed, tracer=obs.Tracer())
    t_end = time.perf_counter() + seconds
    i = 0
    while i == 0 or time.perf_counter() < t_end:
        system.call(i)
        i += 1
    system.release()
    gc.collect()
    s = system.sample(np.random.default_rng(seed))
    return {"seed": seed, "calls": i,
            "program": system.compare(s, system.program_answers(s)),
            "control": system.compare(s, system.control_answers(s)),
            "limits": system.limits}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    run._prepare_environment(cache=True)
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("chipbench.control: no TPU; nothing is measured")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
