"""Device updates the batcher sent outside its step program, per traced
tick: page-table writes and slot resets, each a dispatch of its own
(``BatcherTickEvent.eager_updates`` of ``serving/scheduler.py``; a
program without the counter reads nothing)."""


def read(ctx):
    ev = ctx.info.get("tick_events") or []
    counts = [getattr(e, "eager_updates", None) for e in ev]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
