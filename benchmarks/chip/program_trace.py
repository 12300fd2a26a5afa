"""Reduce a profiler trace to what the program's own spans and scopes say.

    python3 benchmarks/chip/program_trace.py <trace.xplane.pb> [--window NAME]

prints one JSON object: the ``repro.*`` host spans (count, seconds), each
program's device time split by named scope, the idle gaps of the device
named by program span, and the per-layer numbers of ``METRICS`` that the
trace holds.  It reads the ``.xplane.pb`` that ``jax.profiler`` writes,
on any host, without JAX.

Three things come from the file:

- host spans: the events of the ``/host:*`` planes named ``repro.*``
  (``repro/obs/spans.py``), with their ids (``msg_id``, ``worker``) as
  stats, and the window, the host event named ``window``;
- device operations: one event per operation on the ``XLA Ops`` line of
  each ``/device:TPU:<n>`` plane.  The named-scope path of an operation
  (``jit(step)/layers/while/body/attention/dot_general``) is the
  ``tf_op`` stat of its event *metadata*, which ``ProfileData`` does not
  return; so the file is parsed here, by a schema of the few XSpace
  fields read (``_SCHEMA``);
- program executions: the ``XLA Modules`` line, one event per execution.

Each operation's self time (``trace_reduce.self_times``) goes to the
program whose execution holds it and to the scope of its path
(``scope_of``); an operation under none of the scopes counts as
``other``.  The gaps in the first device's busy time are those that
``trace_reduce`` lists; each instant of a gap is charged to the innermost
(shortest) program span covering it, and a gap is named by the span
charged most, or "no program span" where more of it lies under none.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import heapq
import json
import math
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

from trace_reduce import gaps, self_times, stable_name  # noqa: E402

__all__ = ["ProgramTrace", "HostSpan", "reduce_file", "reduce_space",
           "scope_of", "METRICS"]

BLOCK_SCOPES = ("attention", "mlp", "norm")
NO_SPAN = "no program span"

# The fields of tsl/profiler/protobuf/xplane.proto that are read, with
# their numbers there.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "string", False), ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False),
               ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("double_value", 2, "double", False),
              ("uint64_value", 3, "uint64", False),
              ("int64_value", 4, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("name", 2, "string", False), ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
}
_MAPS = {"EventMetadataEntry", "StatMetadataEntry"}
_XSPACE = None


def _xspace_class():
    global _XSPACE
    if _XSPACE is None:
        from google.protobuf import descriptor_pb2, descriptor_pool
        from google.protobuf import message_factory

        F = descriptor_pb2.FieldDescriptorProto
        fdp = descriptor_pb2.FileDescriptorProto(
            name="benchchip_xplane.proto", package="benchchip.xplane",
            syntax="proto3")
        for msg, fields in _SCHEMA.items():
            m = fdp.message_type.add(name=msg)
            if msg in _MAPS:
                m.options.map_entry = True
            if msg == "XStat":
                m.oneof_decl.add(name="value")
            for name, num, typ, rep in fields:
                f = m.field.add(name=name, number=num, label=(
                    F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL))
                if msg == "XStat" and num > 1:  # the oneof ``value``
                    f.oneof_index = 0
                if typ[0].isupper():
                    f.type = F.TYPE_MESSAGE
                    f.type_name = f".benchchip.xplane.{typ}"
                else:
                    f.type = getattr(F, "TYPE_" + typ.upper())
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fdp)
        _XSPACE = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("benchchip.xplane.XSpace"))
    return _XSPACE


def scope_of(path: str) -> str:
    """The scope an operation's ``tf_op`` path puts it under: its innermost
    block scope (``attention``, ``mlp``, ``norm``); else ``layers`` inside
    the layer scan, or ``embed``/``logits``; else ``other``."""
    parts = path.split("/")
    for p in reversed(parts):
        if p in BLOCK_SCOPES:
            return p
    for s in ("layers", "embed", "logits"):
        if s in parts:
            return s
    return "other"


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float  # ns, the profiler's clock
    end: float
    ids: Dict[str, object]


@dataclasses.dataclass
class ProgramTrace:
    window: Tuple[float, float]  # ns
    spans: List[HostSpan]        # repro.* spans that start in the window
    # program -> scope -> device seconds (mean over devices)
    scope_s: Dict[str, Dict[str, float]]
    # program -> [seconds of each execution in the window] (first device)
    runs: Dict[str, List[float]]
    program_gaps: List[list]     # [name, seconds] of the 10 longest gaps
    idle_by_span: Dict[str, float]  # every gap's seconds, by program span

    def span_totals(self) -> Dict[str, Tuple[int, float]]:
        out: Dict[str, list] = {}
        for s in self.spans:
            acc = out.setdefault(s.name, [0, 0.0])
            acc[0] += 1
            acc[1] += (s.end - s.start) * 1e-9
        return {k: (n, t) for k, (n, t) in sorted(out.items())}

    def spans_named(self, name: str) -> List[HostSpan]:
        return [s for s in self.spans if s.name == name]

    def scope_ms(self, program: str, scope: str) -> Optional[float]:
        """Device ms per execution of the programs matching ``program``
        under ``scope``."""
        rx = re.compile(program)
        n = sum(len(v) for k, v in self.runs.items() if rx.search(k))
        if not n:
            return None
        secs = sum(d.get(scope, 0.0) for k, d in self.scope_s.items()
                   if rx.search(k))
        return 1e3 * secs / n


def _stat_value(stat, stat_meta):
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return stat_meta.get(stat.ref_value, "")
    return getattr(stat, kind) if kind else None


def _stats(stats, stat_meta) -> Dict[str, object]:
    return {stat_meta.get(st.metadata_id, ""): _stat_value(st, stat_meta)
            for st in stats}


def reduce_file(path: str, window: str = "bench.window") -> ProgramTrace:
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    return reduce_space(space, window)


def _innermost_charges(spans: List[HostSpan],
                       holes: List[Tuple[float, float]]) -> List[Dict[str, float]]:
    """For each of the sorted, disjoint ``holes``, the ns charged to each
    span name (``NO_SPAN`` for none): every instant goes to the shortest
    span that covers it."""
    points = sorted([(s.start, 1, i) for i, s in enumerate(spans)]
                    + [(s.end, 0, i) for i, s in enumerate(spans)])
    points.append((math.inf, 0, -1))
    out: List[Dict[str, float]] = [{} for _ in holes]
    active: List[Tuple[float, int]] = []  # heap of (duration, span index)
    ended = set()
    h, t = 0, -math.inf
    for p, starts, i in points:
        # charge [t, p) to the innermost active span, within each hole
        while active and active[0][1] in ended:
            heapq.heappop(active)
        name = spans[active[0][1]].name if active else NO_SPAN
        while h < len(holes) and holes[h][1] <= t:
            h += 1
        k = h
        while k < len(holes) and holes[k][0] < p:
            d = min(p, holes[k][1]) - max(t, holes[k][0])
            if d > 0:
                out[k][name] = out[k].get(name, 0.0) + d
            k += 1
        t = p
        if starts:
            heapq.heappush(active, (spans[i].end - spans[i].start, i))
        elif i >= 0:
            ended.add(i)
    return out


def reduce_space(space, window: str = "bench.window") -> ProgramTrace:
    spans: List[HostSpan] = []
    win = None
    dev = []  # per device: (ops [(s, e, tf_op)], modules [(s, e, name)])
    for plane in space.planes:
        stat_meta = {k: v.name for k, v in plane.stat_metadata.items()}
        ev_meta = plane.event_metadata
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                t0 = line.timestamp_ns
                for ev in line.events:
                    name = ev_meta[ev.metadata_id].name
                    if not (name.startswith("repro.") or name == window):
                        continue
                    s = t0 + ev.offset_ps * 1e-3
                    e = s + ev.duration_ps * 1e-3
                    if name == window:
                        if win is None or e - s > win[1] - win[0]:
                            win = (s, e)
                        continue
                    spans.append(HostSpan(name, s, e,
                                          _stats(ev.stats, stat_meta)))
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            tf_op = {}
            for mid, md in ev_meta.items():
                st = _stats(md.stats, stat_meta)
                tf_op[mid] = str(st.get("tf_op", ""))
            ops, mods = [], []
            ln = lines["XLA Ops"]
            for ev in ln.events:
                s = ln.timestamp_ns + ev.offset_ps * 1e-3
                ops.append((s, s + ev.duration_ps * 1e-3,
                            tf_op.get(ev.metadata_id, "")))
            ln = lines.get("XLA Modules")
            for ev in (ln.events if ln is not None else []):
                s = ln.timestamp_ns + ev.offset_ps * 1e-3
                mods.append((s, s + ev.duration_ps * 1e-3,
                             stable_name(ev_meta[ev.metadata_id].name)))
            dev.append((ops, sorted(mods)))

    if win is None:
        ends = [(s, e) for ops, _ in dev for s, e, _ in ops] or [
            (s.start, s.end) for s in spans] or [(0.0, 0.0)]
        win = (min(s for s, _ in ends), max(e for _, e in ends))
    lo, hi = win

    scope_s: Dict[str, Dict[str, float]] = {}
    runs: Dict[str, List[float]] = {}
    n = max(len(dev), 1)
    for d, (ops, mods) in enumerate(dev):
        starts = [m[0] for m in mods]
        evs = []
        for s, e, path in ops:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            j = bisect.bisect_right(starts, s) - 1
            prog = mods[j][2] if j >= 0 and mods[j][1] >= e else "other"
            evs.append((s, e, (prog, scope_of(path))))
        for (prog, scope), t in self_times(evs):
            acc = scope_s.setdefault(prog, {})
            acc[scope] = acc.get(scope, 0.0) + t * 1e-9 / n
        if d == 0:
            for s, e, name in mods:
                if e > lo and s < hi:
                    runs.setdefault(name, []).append((e - s) * 1e-9)

    holes = gaps([(s, e) for s, e, _ in dev[0][0]], lo, hi) if dev else []
    inside = [s for s in spans if s.end > lo and s.start < hi]
    charges = _innermost_charges(inside, holes)
    idle_by_span: Dict[str, float] = {}
    named = []
    for (s, e), ch in zip(holes, charges):
        for k, v in ch.items():
            idle_by_span[k] = idle_by_span.get(k, 0.0) + v * 1e-9
        under = {k: v for k, v in ch.items() if k != NO_SPAN}
        best = max(under, key=under.get) if under else NO_SPAN
        if (e - s) - sum(under.values()) > under.get(best, 0.0):
            best = NO_SPAN
        named.append([best, (e - s) * 1e-9])
    named.sort(key=lambda g: -g[1])
    return ProgramTrace(
        window=win,
        spans=[s for s in spans if lo <= s.start < hi],
        scope_s=scope_s,
        runs=runs,
        program_gaps=named[:10],
        idle_by_span=dict(sorted(idle_by_span.items(), key=lambda kv: -kv[1])),
    )


# ---------------------------------------------------------------------------
# per-layer numbers
# ---------------------------------------------------------------------------


def _mean(xs: List[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


def payload_kernel_ms(pt: ProgramTrace) -> Optional[float]:
    """Mean wall ms of ``repro.payload.kernel``: the payload's kernel call
    from its executor thread, through ``block_until_ready``."""
    return _mean([(s.end - s.start) * 1e-6
                  for s in pt.spans_named("repro.payload.kernel")])


def executor_hop_ms(pt: ProgramTrace) -> Optional[float]:
    """Mean ms by which ``repro.payload.call`` (the event loop's await of
    the executor) outlasts the ``repro.payload.kernel`` of the same
    ``msg_id`` inside it: the hop to the executor thread and back."""
    kernels: Dict[object, List[HostSpan]] = {}
    for k in pt.spans_named("repro.payload.kernel"):
        kernels.setdefault(k.ids.get("msg_id"), []).append(k)
    hops = []
    for c in pt.spans_named("repro.payload.call"):
        for k in kernels.get(c.ids.get("msg_id"), []):
            if c.start <= k.start and k.end <= c.end:
                hops.append(((c.end - c.start) - (k.end - k.start)) * 1e-6)
                break
    return _mean(hops)


def batch_cold_start_ms(pt: ProgramTrace) -> Optional[float]:
    """Mean ms from the start of each ``repro.live.run`` (one batch) to
    the start of its first ``repro.payload.kernel``."""
    ks = sorted(k.start for k in pt.spans_named("repro.payload.kernel"))
    out = []
    for r in pt.spans_named("repro.live.run"):
        j = bisect.bisect_left(ks, r.start)
        if j < len(ks) and ks[j] < r.end:
            out.append((ks[j] - r.start) * 1e-6)
    return _mean(out)


def _scope_reader(program: str, scope: str):
    def read(pt: ProgramTrace) -> Optional[float]:
        return pt.scope_ms(program, scope)

    read.__doc__ = (f"Device ms per execution of ``{program}`` under the "
                    f"``{scope}`` scope.")
    return read


DECODE, PREFILL = r"^jit_step$", r"^jit_prefill$"

METRICS = {
    "payload_kernel_ms": payload_kernel_ms,
    "executor_hop_ms": executor_hop_ms,
    "batch_cold_start_ms": batch_cold_start_ms,
    "decode_attention_ms": _scope_reader(DECODE, "attention"),
    "decode_mlp_ms": _scope_reader(DECODE, "mlp"),
    # inside ``layers`` but under no block scope: the scan's slices and
    # write-backs of the stacked weights and cache
    "decode_layer_stack_ms": _scope_reader(DECODE, "layers"),
    "prefill_attention_ms": _scope_reader(PREFILL, "attention"),
    "prefill_mlp_ms": _scope_reader(PREFILL, "mlp"),
}


def summary(pt: ProgramTrace) -> Dict[str, object]:
    """Everything the reduction found, as plain JSON values."""
    metrics = {k: f(pt) for k, f in METRICS.items()}
    return {
        "window_s": (pt.window[1] - pt.window[0]) * 1e-9,
        "spans": {k: {"count": n, "s": t}
                  for k, (n, t) in pt.span_totals().items()},
        "programs": {k: {"runs": len(v), "device_s": sum(v),
                         "scoped_s": pt.scope_s.get(k, {})}
                     for k, v in sorted(pt.runs.items())},
        "program_gaps": pt.program_gaps,
        "idle_by_span_s": pt.idle_by_span,
        "metrics": {k: v for k, v in metrics.items() if v is not None},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a .xplane.pb written by jax.profiler")
    ap.add_argument("--window", default="bench.window",
                    help="host span that bounds the reduction (default: "
                         "bench.window; the whole trace where absent)")
    args = ap.parse_args(argv)
    print(json.dumps(summary(reduce_file(args.trace, args.window)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
