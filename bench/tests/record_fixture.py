"""Record the small device trace the trace-reduction tests read.

    python -m bench.tests.record_fixture OUT.xplane.pb      # on a TPU

yi-6b at its published widths cut to 2 layers, 4 slots, served through
the chunked engine as the benchmark drives it: after warm-up, the
profiler records two submits and four steps, each inside the
benchmark's host annotations, within one "trace_window" annotation.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile


def main(out: str) -> None:
    from bench import harness, spec, weights as W
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    import jax
    import numpy as np
    from repro.serving.engine import Request
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_fixture: needs a TPU")
    arch = W.arch_config(spec.config("yi-6b")).with_depth(2)
    cell = {"page_size": 16, "slots": 4, "max_len": 256, "chunk_size": 64,
            "step_tokens": 68}
    eng = harness.build_engine(arch, W.build(arch, 1), cell,
                               recorder=False, n_pages=64)
    harness.warm_up(eng, cell, arch.vocab_size)
    rng = np.random.default_rng(1)
    ann = jax.profiler.TraceAnnotation
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with ann("trace_window"):
        with ann("submit"):
            for rid, n in ((0, 100), (1, 40)):
                eng.submit(Request(rid, rng.integers(
                    0, arch.vocab_size, n).astype(np.int32),
                    max_new_tokens=8))
        for _ in range(4):
            with ann("engine.step"):
                eng.step()
        with ann("wait_arrival"):
            import time
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, out)
    shutil.rmtree(d)
    print(f"record_fixture: {os.path.getsize(out)} bytes -> {out}")


if __name__ == "__main__":
    main(sys.argv[1])
