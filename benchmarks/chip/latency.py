"""Per-message latency decomposition from the runtime's event log.

Copied from ``repro.obs.analyze`` (``spans``, ``latency_decomposition``)
so that the arithmetic of the yardstick stays with the benchmark.  Per
completed message, in scenario seconds:

- ``queue_wait``: first ``msg.enqueued`` to the last ``msg.pulled``;
- ``handoff``: last pull to the ``start_t`` stamped on the completion;
- ``service``: ``start_t`` to ``done_t``.
"""

from __future__ import annotations

from typing import Dict, List

__all__ = ["per_message", "mean"]


def per_message(events: List[dict]) -> List[dict]:
    by_msg: Dict[int, List[dict]] = {}
    for e in events:
        if e["ev"].startswith("msg."):
            by_msg.setdefault(e["msg_id"], []).append(e)
    rows = []
    for msg_id in sorted(by_msg):
        evs = by_msg[msg_id]
        enq = next((e for e in evs if e["ev"] == "msg.enqueued"), None)
        done = next((e for e in reversed(evs) if e["ev"] == "msg.completed"),
                    None)
        if enq is None or done is None:
            continue
        pulls = [e for e in evs if e["ev"] == "msg.pulled"]
        last_pull_t = pulls[-1]["t"] if pulls else done["start_t"]
        rows.append({
            "msg_id": msg_id,
            "queue_wait": last_pull_t - enq["t"],
            "handoff": done["start_t"] - last_pull_t,
            "service": done["done_t"] - done["start_t"],
        })
    return rows


def mean(rows: List[dict], key: str) -> float:
    return sum(r[key] for r in rows) / len(rows)
