"""Share of its HBM roofline that the fused cross-entropy kernel
reaches.  Its trace name (checked by hand in a v5e trace) is
``jvp_jit__xent_padded__``; it reads the (tokens, vocab) float32 logits
once (``costs.xent_bytes``)."""
import costs

KERNEL = r"^jvp_jit__xent"


def read(ctx):
    seconds, calls = ctx.trace.op_seconds(KERNEL)
    if not calls:
        return None
    rows = ctx.info["tokens_per_step"]
    vocab = ctx.run.config["vocab_size"]
    least = calls * costs.min_seconds(
        ctx.peak, bytes_=costs.xent_bytes(rows, vocab),
        flops=costs.xent_flops(rows, vocab))
    return 100.0 * least / seconds
