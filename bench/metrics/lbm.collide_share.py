"""Share of the device's busy time spent in the collision kernel; the
rest of a sweep is propagation and the layout transforms around it."""
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    seconds, calls = ctx.trace.op_seconds(KERNEL)
    if not calls or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * seconds / ctx.trace.busy_s
