"""The one traffic generator: reads a traffic file's parameters and makes
the inputs of a run from ``--seed``.

Every seed gets the same set of image durations, in another order: the
quantiles of their distribution, shuffled by the seed.  So two seeds give
the same work, and differ only in the order it comes in.  Kinds:

- ``image_batch``: the paper's Sec. VI-B use case, ``n_images`` images all
  due at t = 0, durations uniform on ``duration_range`` scenario seconds
  (copied from ``repro.scenarios.streams.usecase_workload``, with the
  uniform draws replaced by shuffled quantiles);
- ``lm_static``: static batches of ``batch`` prompts of ``prompt_len``
  tokens drawn uniformly from the model's ``vocab`` real token ids.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from harness import rng

__all__ = ["image_arrivals", "lm_prompts"]


def _uniform_quantiles(n: int, lo: float, hi: float) -> np.ndarray:
    return lo + (hi - lo) * (np.arange(n) + 0.5) / n


def image_arrivals(traffic: Dict[str, Any], seed: int,
                   run: int) -> List[Tuple[float, float, float]]:
    """``(arrival, duration, cpu_cores)`` per image, in arrival order.

    ``run`` numbers the batches of one process (each is shuffled anew).
    """
    kind = traffic["kind"]
    r = rng(seed, 1, run)
    lo, hi = traffic["duration_range"]
    cores = float(traffic.get("cpu_cores", 1.0))
    if kind == "image_batch":
        n = int(traffic["n_images"])
        durs = r.permutation(_uniform_quantiles(n, lo, hi))
        return [(0.0, float(d), cores) for d in durs]
    raise ValueError(f"traffic kind {kind!r} makes no images")


def lm_prompts(traffic: Dict[str, Any], vocab: int, seed: int,
               cycle: int) -> np.ndarray:
    """The ``(batch, prompt_len)`` int32 prompts of one static batch."""
    if traffic["kind"] != "lm_static":
        raise ValueError(f"traffic kind {traffic['kind']!r} makes no prompts")
    return rng(seed, 2, cycle).integers(
        0, int(vocab),
        size=(int(traffic["batch"]), int(traffic["prompt_len"])),
        dtype=np.int32)
