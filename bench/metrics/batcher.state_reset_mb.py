"""Megabytes per traced tick that the batcher's admission resets rewrote:
each reset is an eager ``.at[slot].set`` of a whole, non-donated cache
leaf (``BatcherTickEvent.state_reset_bytes`` of ``serving/scheduler.py``;
a program without the counter reads nothing)."""


def read(ctx):
    ev = ctx.info.get("tick_events") or []
    counts = [getattr(e, "state_reset_bytes", None) for e in ev]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts) / 1e6
