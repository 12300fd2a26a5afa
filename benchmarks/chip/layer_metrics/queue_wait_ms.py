"""Mean wall milliseconds an image waited in the broker's queue, from the
event log by the copied latency decomposition (``latency.py``)."""

import latency


def read(o):
    rows = latency.per_message(o.layer.get("events") or [])
    if not rows:
        return None
    return latency.mean(rows, "queue_wait") * o.layer["time_scale"] * 1e3
