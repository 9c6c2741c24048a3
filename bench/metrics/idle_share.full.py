"""Share of the window in which no operation ran on the device, in %,
averaged over the four chips of the miner's mesh (each chip's own share
is printed on an earlier line)."""


def read(ctx):
    if ctx.reduction is None:
        return None
    return 100.0 * (1.0 - ctx.reduction.mean_busy_s()
                    / ctx.reduction.window_s)
