"""Scheduler: mean over the window's steps of decoding slots over
slots (engine.counters), %."""


def read(ctx):
    c = ctx["run"]["counters"]
    if not c:
        return None
    slots = ctx["cell"]["slots"]
    return 100.0 * sum(x.get("decode_tokens", 0) for x in c) / (len(c) * slots)
