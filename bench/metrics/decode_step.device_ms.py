"""Mean device time of one call of the batcher's decode program
(``jit_decode_step`` in the trace: ``parallel/steps.make_decode_step``)."""
PROGRAM = r"^jit_decode_step$"


def read(ctx):
    seconds, calls = ctx.trace.module_seconds(PROGRAM)
    return 1e3 * seconds / calls if calls else None
