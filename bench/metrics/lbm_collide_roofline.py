"""Share of its HBM roofline that the Pallas BGK collision reaches.

Every ``tpu_custom_call`` of an LBM cell is the collision kernel (the
interior, and on several chips the two boundary slabs of each shard), as
checked by hand in a v5e trace.  Each sweep collides every site of the
chip once, and a site needs 152 B (19 reads and 19 writes of fp32):
``costs.lbm_site_bytes``.
"""
import costs

KERNEL = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    seconds, calls = ctx.trace.op_seconds(KERNEL)
    sweeps = ctx.info["traced_calls"] * ctx.info["sweeps_per_call"]
    if not calls or not sweeps:
        return None
    least = costs.min_seconds(
        ctx.peak, bytes_=sweeps * ctx.info["sites_per_chip"]
        * costs.lbm_site_bytes())
    return 100.0 * least / seconds
