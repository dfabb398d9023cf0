"""Readings that set a cell's limit on the logit gap: the program's on
many seeds and the fp8 control's on the same requests, in one process.

    python -m bench.calibrate --workload yi-6b.chat --seeds 1,2,3 --seconds 10 \
        [--set '{"slots": 8}']

Each seed is a whole run of the cell (weights, warm-up, ramp, a window
of --seconds at the cell's own load) followed by the reference and the
control on the sampled requests.  The control takes the program's place
and is judged by the same limit: `control_correct` has to read false.
One JSON line per seed; `worst` lists each compared request whose gap
exceeds --tol.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", default=None,
                    help="JSON merged into the cell's settings")
    ap.add_argument("--tol", type=float, default=0.1)
    args = ap.parse_args(argv)
    from bench import spec
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    import jax
    from bench import run as R
    from bench.peaks import peaks
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = spec.benchmark()
    work = spec.workload(args.workload, bench)
    work["bench"] = bench
    if args.set:
        work["cell"].update(json.loads(args.set))
    pk = peaks(jax.devices()[0].device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = R.run_cell(work, name=args.workload, seed=seed,
                         seconds=args.seconds, trace=False, t_start=t,
                         peaks=pk, control=True)
        cmp = {k: v for k, v in res["compared"].items()
               if k != "per_request"}
        worst = [r for r in res["compared"]["per_request"]
                 if max(r["gap"], r["control_gap"]) > args.tol]
        print(json.dumps({"seed": seed, **cmp,
                          "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "limit": res["check"]["off_share"]["limit"],
                          "worst": worst,
                          "memory_peak_bytes": res["memory_peak_bytes"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "preemptions": res["run"]["preemptions"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()},
                          "run_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
