"""The decode program's share of the chip's HBM bandwidth: the least bytes
of its calls (the configuration's ``decode_bytes``: the weights once per
call, the live rows' K/V and recurrent state) over its device time.  The
driver counts the traced ticks' live rows and contexts; a configuration
whose reference has no ``decode_bytes`` reads nothing."""
import harness

PROGRAM = r"^jit_decode_step$"


def read(ctx):
    seconds, calls = ctx.trace.module_seconds(PROGRAM)
    ticks = ctx.info.get("decode_ticks")
    if not calls or not ticks:
        return None
    ref = harness.reference(ctx.run.cell["config"])
    if not hasattr(ref, "decode_bytes"):
        return None
    cfg = ctx.run.config
    # decode_bytes is the weights plus terms linear in rows and contexts.
    weights = ref.decode_bytes(cfg, 0, 0)
    per_call = (ref.decode_bytes(cfg, ctx.info["decode_rows"],
                                 ctx.info["decode_ctx"])
                + (ticks - 1) * weights) / ticks
    return 100.0 * per_call * calls / (seconds * ctx.peak["hbm_bytes_per_s"])
