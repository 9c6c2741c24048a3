"""Device time of the optimal-mode search program in the window over the
nonces it searched, in ns per nonce.  The program is the executor's
single-device reducer (``jit_reduce_all``); each run searches the whole
nonce space."""

PROGRAM = "jit_reduce_all"


def read(ctx):
    if ctx.reduction is None:
        return None
    seconds, count = ctx.reduction.program_time(PROGRAM)
    if count == 0:
        return None
    return seconds / (count * ctx.system.args_per_block) * 1e9
