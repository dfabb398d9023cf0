"""Per-layer metric readers, one file per metric, found by its name.

Each file defines `read(ctx) -> float | None`.  `ctx` holds "cfg" (the
configuration file), "cell", "peaks" (or None off a chip), "run" (the
harness's raw records, bench.harness.drive) and "trace" (the reduced
profiler trace, bench.xplane.reduce, or None).  A reader that finds
nothing to read returns None and the metric is left out of the line.
"""
