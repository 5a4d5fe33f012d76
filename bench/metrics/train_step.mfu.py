"""The train step's share of the chip's bf16 peak: forward and backward
operations per token (the configuration's ``train_flops_per_token``:
causal attention and the LM head counted, recomputation not) times the
tokens of the traced steps, over the traced window."""
import harness


def read(ctx):
    steps = ctx.info.get("traced_steps")
    if not steps:
        return None
    ref = harness.reference(ctx.run.cell["config"])
    cell = ctx.run.cell["training"]
    flops = (ref.train_flops_per_token(ctx.run.config, cell["seq_len"])
             * steps * ctx.info["tokens_per_step"])
    return 100.0 * flops / (ctx.trace.window_s
                            * ctx.peak["bf16_flops_per_s"])
