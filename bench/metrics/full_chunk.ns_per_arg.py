"""Device time of the full-mode chunk program (``jit_eval_chunk``: jash,
submission hash and leaf digest of one chunk) in the window on the
busiest chip of the miner's mesh, over the args of the window's blocks,
in ns per arg: the mesh's device time per arg, which rises if the
chunks' work is not spread over the chips."""

PROGRAM = "jit_eval_chunk"


def read(ctx):
    if ctx.reduction is None:
        return None
    busiest = max(d.programs.get(PROGRAM, (0.0, 0))[0]
                  for d in ctx.reduction.devices.values())
    if busiest <= 0:
        return None
    return busiest / (len(ctx.records) * ctx.system.args_per_block) * 1e9
