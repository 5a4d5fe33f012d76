"""The decode program's share of the chip's bf16 peak: the model
operations of its live rows (the configuration's ``decode_flops``:
padding rows and masked keys are not work) over its device time."""
import harness

PROGRAM = r"^jit_decode_step$"


def read(ctx):
    seconds, calls = ctx.trace.module_seconds(PROGRAM)
    if not calls or not ctx.info.get("decode_ticks"):
        return None
    ref = harness.reference(ctx.run.cell["config"])
    flops = ref.decode_flops(ctx.run.config, ctx.info["decode_rows"],
                             ctx.info["decode_ctx"])
    return 100.0 * flops / (seconds * ctx.peak["bf16_flops_per_s"])
