"""The stream's guarantees, checked from what the run recorded.

At-least-once delivery: every image the generator sent completed at least
once.  FIFO: the broker hands out one image's messages in the order they
arrived (``runtime/master.py``), so along the order in which the
generator pushed them, their start times never go down.  It imports
nothing of the program: it reads ``(msg_id, start_t, done_t)`` per
completion and the sent ids in push order.
"""

from __future__ import annotations

from typing import Dict, Iterable, Sequence

__all__ = ["undelivered", "fifo_violations"]


def undelivered(sent: Sequence[int], completed: Iterable[int]) -> int:
    """How many sent ids never completed."""
    done = set(completed)
    return sum(1 for m in sent if m not in done)


def fifo_violations(sent: Sequence[int],
                    starts: Dict[int, float]) -> int:
    """How many sent messages started before one pushed ahead of them."""
    bad, latest = 0, float("-inf")
    for m in sent:
        if m not in starts:
            continue
        if starts[m] < latest:
            bad += 1
        latest = max(latest, starts[m])
    return bad

