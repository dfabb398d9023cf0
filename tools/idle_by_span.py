"""Idle device time by the serving step's innermost program span.

    python tools/idle_by_span.py TRACE.xplane.pb

Reduces a profiler trace with `bench.xplane.reduce`, each idle gap of
the device labelled by the innermost of the engine's step spans
(`repro.serving.engine.STEP_SPANS`, profiler annotations every serving
step carries) that covers the gap's middle, over the window of the
trace's first `trace_window` annotation when it has one, else from the
first to the last device event.  Prints idle seconds and gap counts by
span, largest first; "none" is idle time outside every step span.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def idle_by_span(pd) -> dict:
    """`bench.xplane.reduce`'s summary of ProfileData `pd` under the
    step spans, with `bench.xplane.breakdown`'s `idle_gaps` of it:
    ["<span> (<n> gaps)", idle seconds], largest first."""
    from bench import xplane
    from repro.serving.engine import STEP_SPANS
    summary = xplane.reduce(pd, STEP_SPANS,
                            window=xplane.host_window(pd, "trace_window"))
    summary["idle_gaps"] = xplane.breakdown(
        summary, n=len(STEP_SPANS) + 1)["idle_gaps"]
    return summary


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.strip().split("\n\n")[1], file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import xplane
    summary = idle_by_span(xplane.load(argv[0]))
    total = sum(s for _, s in summary["idle"])
    print(f"window {summary['window_s']:.6f} s, device busy "
          f"{summary['busy_s']:.6f} s, idle {total:.6f} s")
    for label, s in summary["idle_gaps"]:
        share = 100.0 * s / total if total else 0.0
        print(f"{label:<36} {s:>10.6f} s {share:>6.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
