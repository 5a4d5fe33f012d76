"""Share of the batcher's occupied rows that were teacher-forcing a
prompt rather than decoding, over the traced ticks (``BatcherTickEvent``
counters of ``serving/scheduler.py``)."""


def read(ctx):
    ev = ctx.info.get("tick_events") or []
    pre = sum(e.n_prefill for e in ev)
    busy = pre + sum(e.n_decode for e in ev)
    return 100.0 * pre / busy if busy else None
