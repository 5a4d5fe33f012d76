"""Share of the device's busy time spent in the LBM kernel (on one chip
the fused pull+collide sweep, ``lbm_collide.N``); the rest is what XLA
runs outside it, such as the driver's own fusions and, where a sweep is
not fused, propagation and the layout transforms."""
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    seconds, calls = ctx.trace.op_seconds(KERNEL)
    if not calls or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
