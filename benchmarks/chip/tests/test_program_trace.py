"""The reduction of the program's spans and scopes (``program_trace.py``).

Checked on traces made here, field by field, in the XSpace layout that
``jax.profiler`` writes; on ``data/small.xplane.pb`` (see
``test_trace_reduce.py``), where it must find the gaps that
``trace_reduce`` finds; and on ``data/spans.xplane.pb``, recorded on one
v5e by ``record_spans.py``, which holds the program's spans and scopes."""

from __future__ import annotations

import pytest

import program_trace as pt
import trace_reduce as tr

DATA = tr.__file__.rsplit("/", 1)[0] + "/tests/data/small.xplane.pb"
MS = 1_000_000  # ns


def make_space(host=(), ops=(), modules=(), window=None):
    """An XSpace of one host plane and one TPU plane.

    ``host``: ``(name, start_ns, end_ns, {stat: int})``; ``ops``:
    ``(start_ns, end_ns, tf_op)``; ``modules``: ``(start_ns, end_ns,
    name)``; ``window``: ``(start_ns, end_ns)`` of ``bench.window``."""
    space = pt._xspace_class()()
    cpu = space.planes.add(name="/host:CPU")
    meta = {}

    def ev_id(plane, name):
        key = (plane.name, name)
        if key not in meta:
            meta[key] = len(meta) + 1
            plane.event_metadata[meta[key]].name = name
        return meta[key]

    stat_ids = {}

    def stat_id(plane, name):
        if name not in stat_ids:
            stat_ids[name] = len(stat_ids) + 100
            plane.stat_metadata[stat_ids[name]].name = name
        return stat_ids[name]

    line = cpu.lines.add(name="python", timestamp_ns=0)
    rows = list(host) + ([("bench.window", *window, {})] if window else [])
    for name, s, e, ids in rows:
        ev = line.events.add(metadata_id=ev_id(cpu, name), offset_ps=s * 1000,
                             duration_ps=(e - s) * 1000)
        for k, v in ids.items():
            ev.stats.add(metadata_id=stat_id(cpu, k), int64_value=v)
    tpu = space.planes.add(name="/device:TPU:0")
    mline = tpu.lines.add(name="XLA Modules", timestamp_ns=0)
    for s, e, name in modules:
        mline.events.add(metadata_id=ev_id(tpu, name), offset_ps=s * 1000,
                         duration_ps=(e - s) * 1000)
    oline = tpu.lines.add(name="XLA Ops", timestamp_ns=0)
    tf_op = tpu.stat_metadata[1]
    tf_op.name = "tf_op"
    for i, (s, e, path) in enumerate(ops):
        mid = 1000 + i
        md = tpu.event_metadata[mid]
        md.name = f"%op.{i}"
        if i % 2:  # the string by reference, as the profiler also writes it
            tpu.stat_metadata[5000 + i].name = path
            md.stats.add(metadata_id=1, ref_value=5000 + i)
        else:
            md.stats.add(metadata_id=1, str_value=path)
        oline.events.add(metadata_id=mid, offset_ps=s * 1000,
                         duration_ps=(e - s) * 1000)
    return space


def test_scope_of_takes_the_innermost_block_scope():
    assert pt.scope_of("jit(step)/layers/while/body/attention/dot_general:"
                       ) == "attention"
    assert pt.scope_of("jit(step)/layers/while/body/mlp/dot_general") == "mlp"
    assert pt.scope_of("jit(f)/layers/while/body/mlp/norm/mul") == "norm"
    assert pt.scope_of("jit(step)/layers/while/body/dynamic_slice") == "layers"
    assert pt.scope_of("jit(step)/embed/gather") == "embed"
    assert pt.scope_of("jit(step)/logits/dot_general") == "logits"
    assert pt.scope_of("jit(step)/norm/rsqrt") == "norm"
    assert pt.scope_of("jit(<lambda>)/dot_general:") == "other"
    assert pt.scope_of("") == "other"
    assert pt.scope_of("jit(step)/normal/add") == "other"


def _decode_trace():
    body = "jit(step)/layers/while/body/"
    ops = []
    mods = []
    for k in range(2):  # two executions of jit_step, 10 ms each
        t = k * 20 * MS
        mods.append((t, t + 10 * MS, "jit_step(123)"))
        ops += [(t, t + 1 * MS, "jit(step)/embed/gather"),
                # a while holding the layer stack: its self time is the
                # scan's own, its children the blocks
                (t + 1 * MS, t + 8 * MS, body[:-1]),
                (t + 2 * MS, t + 4 * MS, body + "attention/dot_general"),
                (t + 4 * MS, t + 7 * MS, body + "mlp/dot_general"),
                (t + 7 * MS, t + 8 * MS, body + "norm/rsqrt"),
                (t + 8 * MS, t + 9 * MS, "jit(step)/logits/dot_general"),
                (t + 9 * MS, t + 10 * MS, "jit(step)/argmax")]
    mods.append((40 * MS, 41 * MS, "jit_prefill(9)"))
    ops.append((40 * MS, 41 * MS, "jit(prefill)/layers/while/body/"
                                  "attention/while/body/exp"))
    return pt.reduce_space(make_space(ops=ops, modules=mods))


def test_device_time_is_split_by_program_and_scope():
    t = _decode_trace()
    assert t.runs["jit_step"] == pytest.approx([0.010, 0.010])
    got = {k: pytest.approx(v) for k, v in t.scope_s["jit_step"].items()}
    assert got == {"embed": 0.002, "layers": 0.002, "attention": 0.004,
                   "mlp": 0.006, "norm": 0.002, "logits": 0.002,
                   "other": 0.002}
    # every op's self time is counted once: the scopes add up to the
    # programs' device time
    assert sum(t.scope_s["jit_step"].values()) == pytest.approx(
        sum(t.runs["jit_step"]))
    assert t.scope_s["jit_prefill"] == {"attention": pytest.approx(0.001)}


def test_scope_readers_give_ms_per_execution():
    t = _decode_trace()
    read = {k: f(t) for k, f in pt.METRICS.items()}
    assert read["decode_attention_ms"] == pytest.approx(2.0)
    assert read["decode_mlp_ms"] == pytest.approx(3.0)
    assert read["decode_layer_stack_ms"] == pytest.approx(1.0)
    assert read["prefill_attention_ms"] == pytest.approx(1.0)
    assert read["prefill_mlp_ms"] == 0.0
    # no host spans: the payload's readers find nothing
    for k in ("payload_kernel_ms", "executor_hop_ms", "batch_cold_start_ms"):
        assert read[k] is None
    assert pt.summary(t)["metrics"].keys() == {
        k for k, v in read.items() if v is not None}


def _stream_trace():
    host = [
        # two batches
        ("repro.live.run", 0, 100 * MS, {}),
        ("repro.live.setup", 0, 5 * MS, {}),
        ("repro.worker.boot", 5 * MS, 45 * MS, {"worker": 0}),
        ("repro.irm.step", 20 * MS, 21 * MS, {}),
        ("repro.pe.start", 45 * MS, 50 * MS, {}),
        ("repro.payload.call", 50 * MS, 60 * MS, {"msg_id": 0}),
        ("repro.payload.kernel", 52 * MS, 58 * MS, {"msg_id": 0}),
        ("repro.payload.pad", 60 * MS, 90 * MS, {}),
        ("repro.live.shutdown", 90 * MS, 100 * MS, {}),
        ("repro.live.run", 200 * MS, 300 * MS, {}),
        ("repro.payload.call", 230 * MS, 250 * MS, {"msg_id": 7}),
        ("repro.payload.kernel", 233 * MS, 247 * MS, {"msg_id": 7}),
        # the same msg_id outside its call is not joined to it
        ("repro.payload.kernel", 251 * MS, 252 * MS, {"msg_id": 0}),
        ("repro.live.run", 2000 * MS, 2100 * MS, {}),  # after the window
    ]
    ops = [(53 * MS, 57 * MS, "k"), (234 * MS, 246 * MS, "k"),
           (251 * MS, 252 * MS, "k")]
    mods = [(53 * MS, 57 * MS, "jit_gmm(1)"), (234 * MS, 246 * MS, "jit_gmm(1)"),
            (251 * MS, 252 * MS, "jit_gmm(1)")]
    return pt.reduce_space(make_space(host, ops, mods, window=(0, 400 * MS)))


def test_spans_are_kept_with_their_ids_inside_the_window():
    t = _stream_trace()
    totals = t.span_totals()
    assert totals["repro.live.run"] == (2, pytest.approx(0.2))
    assert totals["repro.payload.kernel"][0] == 3
    assert [s.ids for s in t.spans_named("repro.payload.call")] == [
        {"msg_id": 0}, {"msg_id": 7}]


def test_payload_readers():
    t = _stream_trace()
    assert pt.payload_kernel_ms(t) == pytest.approx((6 + 14 + 1) / 3)
    assert pt.executor_hop_ms(t) == pytest.approx((4 + 6) / 2)
    # batch 1: 52 ms to its first kernel; batch 2: 33 ms
    assert pt.batch_cold_start_ms(t) == pytest.approx((52 + 33) / 2)


def test_idle_gaps_are_named_by_the_innermost_program_span():
    t = _stream_trace()
    # the device's gaps: [0, 53), [57, 234), [246, 251), [252, 400) ms
    by_len = sorted(t.program_gaps, key=lambda g: -g[1])
    assert [g[1] for g in by_len] == pytest.approx([0.177, 0.148, 0.053,
                                                    0.005])
    names = {round(s * 1e3): n for n, s in t.program_gaps}
    # [57, 234): 30 ms pad, 10 shutdown, 30 of the second run before its
    # call, 7 of calls and kernels, 100 under no span: none covers most
    assert names[177] == pt.NO_SPAN
    # [252, 400): 48 ms under the second run, 100 ms under none
    assert names[148] == pt.NO_SPAN
    # [0, 53): setup 5, boot 40 (the irm.step inside it takes 1), pe
    # start 5, call 3 (1 of it before the kernel's span starts)
    assert names[53] == "repro.worker.boot"
    # [246, 251): the kernel's last 1 ms, its call's 3, the run's 1
    assert names[5] == "repro.payload.call"
    idle = t.idle_by_span
    assert sum(idle.values()) == pytest.approx(0.383)
    assert idle["repro.worker.boot"] == pytest.approx(0.039)
    assert idle["repro.irm.step"] == pytest.approx(0.001)
    assert idle["repro.payload.pad"] == pytest.approx(0.030)
    assert idle["repro.payload.call"] == pytest.approx(0.002 + 0.002 + 0.003
                                                       + 0.003)
    assert idle[pt.NO_SPAN] == pytest.approx(0.200)


def test_recorded_trace_gaps_match_trace_reduce():
    small = tr.reduce_file(DATA)
    t = pt.reduce_file(DATA)
    assert [s for _, s in t.program_gaps] == pytest.approx(
        [s for _, s in small.idle_gaps], abs=1e-8)
    assert [n for n, _ in t.program_gaps] == [pt.NO_SPAN] * 10
    assert {k: len(v) for k, v in t.runs.items()} == {
        k: len(v) for k, v in small.modules.items()}
    # the recorded programs have no scopes: all their time is ``other``
    for prog, v in t.scope_s.items():
        assert set(v) == {"other"}
    secs, _ = small.op_time(".")
    assert sum(sum(v.values()) for v in t.scope_s.values()) == pytest.approx(
        secs, rel=1e-4)


SPANS = tr.__file__.rsplit("/", 1)[0] + "/tests/data/spans.xplane.pb"


@pytest.fixture(scope="module")
def spans_trace():
    return pt.reduce_file(SPANS)


def test_recorded_spans_trace_holds_every_program_span(spans_trace):
    # recorded on one v5e by record_spans.py: a live run of 8 images
    # through the jax payload, then a tiny decoder's prefill and 2 steps
    counts = {k: n for k, (n, _) in spans_trace.span_totals().items()}
    assert counts == {
        "repro.live.run": 1, "repro.live.setup": 1, "repro.live.shutdown": 2,
        "repro.irm.step": 67, "repro.worker.boot": 4, "repro.pe.start": 4,
        "repro.payload.call": 8, "repro.payload.kernel": 8,
        "repro.payload.pad": 8}
    boots = spans_trace.spans_named("repro.worker.boot")
    assert sorted(s.ids["worker"] for s in boots) == [0, 1, 2, 3]
    calls = {s.ids["msg_id"] for s in spans_trace.spans_named(
        "repro.payload.call")}
    assert calls == {s.ids["msg_id"] for s in spans_trace.spans_named(
        "repro.payload.kernel")}
    read = {k: f(spans_trace) for k, f in pt.METRICS.items()}
    assert read["payload_kernel_ms"] == pytest.approx(1.28679725)
    assert read["executor_hop_ms"] == pytest.approx(1.125499625)
    assert read["batch_cold_start_ms"] == pytest.approx(211.633041)
    assert spans_trace.program_gaps[0][0] == "repro.worker.boot"


def test_recorded_spans_trace_scopes_and_kernel_name(spans_trace):
    for prog in ("jit_prefill", "jit_step"):
        assert set(spans_trace.scope_s[prog]) == {
            "embed", "layers", "norm", "attention", "mlp", "logits", "other"}
    assert spans_trace.scope_ms("^jit_step$", "attention") == pytest.approx(
        0.010360078)
    # the kernel's op keeps the name gmm_roofline matches
    secs, n = tr.reduce_file(SPANS).op_time("^grouped_matmul$")
    assert n == 9 and secs > 0
