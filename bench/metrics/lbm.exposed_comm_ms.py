"""Collective time per sweep during which nothing else ran on the chip:
the part of the halo exchange the interior collision did not hide."""
COLLECTIVE = r"collective-permute|all-gather|all-reduce|all-to-all"


def read(ctx):
    sweeps = ctx.info["traced_calls"] * ctx.info["sweeps_per_call"]
    if ctx.info["chips"] < 2 or not sweeps:
        return None
    return 1e3 * ctx.trace.exposed_seconds(COLLECTIVE) / sweeps
