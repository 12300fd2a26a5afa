"""Reduce a profiler trace (``.xplane.pb``) to the per-layer numbers.

What it reads, all from ``jax.profiler.ProfileData``:

- device planes: ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
  one event per operation run on the chip and the ``XLA Modules`` line one
  event per execution of a compiled program;
- the host plane ``/host:CPU``: the benchmark's own annotations
  (``bench.*``) among the host threads' events.

The window is the host event named ``window`` (``bench.window``); every
interval is clipped to it.  (Device and host timestamps share one time
base; on the recorded v5e trace the device's lag about 1.1 ms behind the
host's, which shifts the edges of a window by that much.)  Busy time is
the union of a device's operation intervals, averaged over the devices;
idle gaps are the holes in that union, each named by the benchmark
annotation that overlaps it most.  Operations are totalled by self time
(an operation's time less that of the operations nested in it, as a
``while`` holds its body) under a stable name: the HLO instruction's name
with its ``.<n>`` numbering cut, so that ``%fusion.12 = ...`` and
``%fusion.40 = ...`` count together as ``fusion`` and a Pallas kernel
counts under its kernel's name (``grouped_matmul``).  Programs are
totalled by module name with the fingerprint cut (``jit_step``).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

__all__ = ["TraceSummary", "reduce_file", "reduce_profile", "stable_name",
           "self_times", "union_length", "gaps"]

_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")
_HLO = re.compile(r"^%?([\w.\-]+) = ")


def stable_name(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion``;
    ``jit_step(7823)`` -> ``jit_step``."""
    m = _HLO.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def self_times(events: List[Tuple[float, float, str]]) -> List[Tuple[str, float]]:
    """``(name, self time)`` of ``(start, end, name)`` events of one line,
    where an event that lies within another is its child."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []  # [end, name, self time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][0]:
            top = stack.pop()
            out.append((top[1], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out.extend((t[1], t[2]) for t in stack)
    return out


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by ``[start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The holes in the union of ``intervals`` within ``[lo, hi)``."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class TraceSummary:
    n_devices: int
    window_s: float
    busy_s: float                      # mean over devices
    ops: Dict[str, Tuple[float, int]]  # stable name -> (seconds, count), all devices
    modules: Dict[str, List[float]]    # program name -> execution seconds
    idle_gaps: List[Tuple[str, float]]  # longest first, at most 10
    spans: Dict[str, Tuple[float, int]]  # bench.* host spans -> (seconds, count)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        rows = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:n]
        return [[name, secs / self.n_devices] for name, (secs, _) in rows]

    def op_time(self, pattern: str) -> Tuple[float, int]:
        """Seconds (per device) and count of ops whose name matches."""
        if not self.n_devices:
            return 0.0, 0
        rx = re.compile(pattern)
        secs = sum(v[0] for k, v in self.ops.items() if rx.search(k))
        cnt = sum(v[1] for k, v in self.ops.items() if rx.search(k))
        return secs / self.n_devices, cnt // self.n_devices

    def module_times(self, pattern: str) -> List[float]:
        rx = re.compile(pattern)
        out: List[float] = []
        for k, v in self.modules.items():
            if rx.search(k):
                out.extend(v)
        return out


def reduce_profile(prof, window: str = "bench.window") -> TraceSummary:
    host_events = []
    win = None
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_events.append(
                            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
                        if ev.name == window and (
                                win is None or ev.duration_ns > win[1] - win[0]):
                            win = (ev.start_ns, ev.start_ns + ev.duration_ns)

    dev_planes = [p for p in prof.planes if p.name.startswith("/device:TPU:")
                  and any(ln.name == "XLA Ops" for ln in p.lines)]
    per_dev: List[List[Tuple[float, float]]] = []
    ops: Dict[str, List[float]] = {}
    modules: Dict[str, List[float]] = {}
    for plane in dev_planes:
        ivs: List[Tuple[float, float]] = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                evs = []
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if win is not None:
                        s, e = max(s, win[0]), min(e, win[1])
                        if e <= s:
                            continue
                    evs.append((s, e, stable_name(ev.name)))
                ivs.extend((s, e) for s, e, _ in evs)
                for name, t in self_times(evs):
                    acc = ops.setdefault(name, [0.0, 0])
                    acc[0] += t * 1e-9
                    acc[1] += 1
            elif line.name == "XLA Modules":
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if win is not None and (e <= win[0] or s >= win[1]):
                        continue
                    modules.setdefault(stable_name(ev.name), []).append(
                        ev.duration_ns * 1e-9)
        per_dev.append(ivs)

    n = max(len(dev_planes), 1)
    if win is None:
        starts = [s for ivs in per_dev for s, _ in ivs]
        ends = [e for ivs in per_dev for _, e in ivs]
        win = (min(starts), max(ends)) if starts else (0.0, 0.0)
    busy = sum(union_length(ivs) for ivs in per_dev) / n

    holes = gaps(per_dev[0], *win) if per_dev else []
    holes.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in holes[:10]:
        # the span that covers most of the gap (of equals, the shortest),
        # unless more of the gap lies under no span at all
        best, key, covered = "no benchmark span", (0.0, 0.0), []
        for name, hs, he in host_events:
            ov = min(e, he) - max(s, hs)
            if name != window and ov > 0:
                covered.append((max(s, hs), min(e, he)))
                if (ov, hs - he) > key:
                    best, key = name, (ov, hs - he)
        if (e - s) - union_length(covered) > key[0]:
            best = "no benchmark span"
        named.append([best, (e - s) * 1e-9])

    spans: Dict[str, List[float]] = {}
    for name, hs, he in host_events:
        acc = spans.setdefault(name, [0.0, 0])
        acc[0] += (he - hs) * 1e-9
        acc[1] += 1
    return TraceSummary(
        n_devices=len(dev_planes),
        window_s=(win[1] - win[0]) * 1e-9,
        busy_s=busy * 1e-9,
        ops={k: (v[0], v[1]) for k, v in ops.items()},
        modules=modules,
        idle_gaps=named,
        spans={k: (v[0], v[1]) for k, v in spans.items()},
    )


def reduce_file(path: str, window: str = "bench.window") -> TraceSummary:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path), window)
