"""KV manager: pages the window took from the prefix index over all
pages it acquired (window delta of pool.shares / (pool.allocs +
pool.shares)), %."""


def read(ctx):
    p = ctx["run"]["pool"]
    total = p["allocs"] + p["shares"]
    return None if not total else 100.0 * p["shares"] / total
