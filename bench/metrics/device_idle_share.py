"""Device: 1 - (union of device op intervals / traced window), %."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"] or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
