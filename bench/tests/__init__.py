"""CPU tests of the benchmark harness (collected by the tier-1 run)."""
