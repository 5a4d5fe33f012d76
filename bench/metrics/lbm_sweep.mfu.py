"""The whole sweep's share of the chip's peak: the least time the sweeps
of the traced window could take at the HBM bandwidth (152 B per site,
the sweep's minimum, ``costs.lbm_site_bytes``) over the traced window.
A sweep is bandwidth-bound, so its peak is the HBM roofline."""
import costs


def read(ctx):
    sweeps = ctx.info["traced_calls"] * ctx.info["sweeps_per_call"]
    if not sweeps:
        return None
    least = costs.min_seconds(
        ctx.peak, bytes_=sweeps * ctx.info["sites_per_chip"]
        * costs.lbm_site_bytes())
    return 100.0 * least / ctx.trace.window_s
