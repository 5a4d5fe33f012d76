"""The scheduler's host time per tick: the mean, over the ``batcher.tick``
spans wholly inside the traced window, of the tick's duration less its
``batcher.sync`` child (the wait for the decode program's tokens).  In
that time the next decode program cannot start.  The spans are the
program's own (``repro.obs.SPAN_NAMES``); a program without them reads
nothing."""


def read(ctx):
    red = ctx.trace
    ticks = [e for e in red.host if e.name == "batcher.tick"
             and red.t0 <= e.start and e.end <= red.t1]
    if not ticks:
        return None
    syncs = [e for e in red.host if e.name == "batcher.sync"]
    host = sum(t.end - t.start - sum(
        s.end - s.start for s in syncs
        if t.start <= s.start and s.end <= t.end)
        for t in ticks)
    return host / len(ticks) / 1e6
