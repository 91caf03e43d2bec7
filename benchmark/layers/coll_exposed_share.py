"""ZeRO and comm: the part of collective time during which no other
operation ran on that device, over the traced window (step time)."""


def read(ctx):
    c, t = ctx["trace"]["collectives"], ctx["trace"]
    return 100.0 * c["exposed_s"] / t["window_s"] if c["total_s"] else None
