"""Driver for a stream cell: images through the live runtime, each message
running the grouped-matmul Pallas kernel on the chip.

The timed path is ``repro.runtime.run_live`` on the in-process transport
with the ``jax`` payload, under one IRM whose profiler persists from run
to run, as in the paper's repeated runs.  Set-up builds the payload once
(the program compiles and checks its kernel there), hands it operands made
from the seed, and runs the traffic's warm-up batches through the same
IRM.  The window runs back-to-back batches until its time is up;
``images_per_s`` is every image completed over the wall time of the whole
batches run.

``correct``: every image sent completed (at-least-once delivery), every
image started in the broker's FIFO order, and the kernel's outputs of a
sample of messages, drawn from the seed, match the plain float32 grouped
matmul (``reference/gmm.py``) of the same operands.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List

import numpy as np

import flops
import generator
import harness
from harness import Check, Outcome, Profile
from reference import delivery
from reference.gmm import grouped_matmul


def _stream(arrivals, image: str):
    from repro.scenarios.streams import Message, Stream

    batches: Dict[float, list] = {}
    for t, dur, cores in arrivals:
        batches.setdefault(t, []).append(
            Message(image=image, duration=dur, cpu_cores=cores, arrival=t))
    return Stream(batches=sorted(batches.items()))


class Sampler:
    """Keeps the payload's output of the sampled calls (and of the last).

    Wraps the payload's per-message compute from outside: the program
    calls ``payload._compute`` from its executor threads."""

    def __init__(self, payload, picks, prof: Profile):
        self.inner = payload._compute
        self.picks = set(int(i) for i in picks)
        self.kept: Dict[int, Any] = {}
        self.last = None
        self.calls = itertools.count()
        self.lock = threading.Lock()
        self.prof = prof
        payload._compute = self

    def __call__(self):
        with self.prof.span("bench.payload_compute"):
            out = self.inner()
        i = next(self.calls)
        with self.lock:
            if i in self.picks:
                self.kept[i] = out
            self.last = out
        return out

    def reset(self) -> None:
        self.calls = itertools.count()
        self.kept.clear()


def give_operands(payload, pk: Dict[str, Any], seed: int):
    """Standard-normal float32 operands of the payload's shapes, made on
    the device from the seed, put in place of the payload's own; every
    row of every group valid."""
    import jax
    import jax.numpy as jnp

    E, C, D = pk["experts"], pk["rows"], pk["dim"]

    def make(key):
        kx, kw = jax.random.split(key)
        return (jax.random.normal(kx, (E, C, D), jnp.float32),
                jax.random.normal(kw, (E, D, D), jnp.float32))

    payload._x, payload._w = jax.jit(make)(harness.jax_key(seed, 5))
    payload._sizes = jnp.full((E,), C, jnp.int32)
    return payload._x, payload._w, payload._sizes


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t0: float, devs) -> Outcome:
    from repro.core.irm import IRM, IRMConfig
    from repro.core.sim import SimConfig
    from repro.obs import EventBus
    from repro.runtime import payloads
    from repro.runtime.live import RuntimeConfig, run_live

    t_enter = time.perf_counter()
    cfg, traffic = cell.config, cell.traffic
    ts = float(cfg["time_scale"])
    pk = dict(cfg["payload"])
    name = pk.pop("name")
    payload = payloads.make_payload(name, **pk)  # compiles, checks its kernel

    E, C, D = pk["experts"], pk["rows"], pk["dim"]
    x, w, sizes = give_operands(payload, pk, seed)
    prof = Profile(trace)
    sampler = Sampler(payload, harness.rng(seed, 6).choice(
        int(traffic["sample_range"]), size=int(traffic["sample_calls"]),
        replace=False), prof)
    # run_live builds its payload by name: give it the one built here
    payloads.PAYLOADS[f"bench-{name}"] = lambda: payload

    sim_cfg = SimConfig(**cfg["cluster"])
    sim_cfg.seed = int(harness.rng(seed, 8).integers(2**31 - 1))
    irm_cfg = IRMConfig()
    irm_cfg.allocator.algorithm = cfg["irm"]["algorithm"]
    irm = IRM(irm_cfg)
    rt = RuntimeConfig(time_scale=ts, payload=f"bench-{name}",
                       transport=cfg["transport"])
    image = traffic["image"]
    if trace:
        step = irm.step

        def annotated(*a, **k):
            with prof.span("bench.irm_step"):
                return step(*a, **k)

        irm.step = annotated

    def one_run(arrivals, stats=None, bus=None):
        """Runs the stream; returns its messages in push order."""
        stream = _stream(arrivals, image)
        with prof.span("bench.run_live"):
            run_live(stream, sim_cfg, irm=irm, runtime=rt, stats=stats,
                     bus=bus)
        return [m for _, b in stream.batches for m in b]

    t_built = time.perf_counter()
    # warm-up through the same IRM: the batches before the window
    for i in range(int(traffic["warmup_batches"])):
        one_run(generator.image_arrivals(traffic, seed, -1 - i))

    sampler.reset()
    runs: List[List[Any]] = []  # each run's messages, in push order
    stats: List[Dict[str, Any]] = []
    bus = EventBus(level="lifecycle")
    prof.start()
    t_start = time.perf_counter()
    setup_s = t_start - t0
    batch_s: List[float] = []
    with prof.span(Profile.WINDOW):
        while time.perf_counter() - t_start < seconds:
            st: Dict[str, Any] = {}
            t_batch = time.perf_counter()
            runs.append(one_run(
                generator.image_arrivals(traffic, seed, len(runs)), st,
                bus if trace else None))
            batch_s.append(time.perf_counter() - t_batch)
            stats.append(st)
    window_s = time.perf_counter() - t_start
    prof.stop()
    peak = harness.memory_peak_bytes(devs)

    msgs = [m for r in runs for m in r]
    done = [m for m in msgs if m.done_t >= 0]
    undelivered = fifo = 0
    for r in runs:  # each run's clock starts at 0
        sent = [m.msg_id for m in r]
        starts = {m.msg_id: m.start_t for m in r if m.done_t >= 0}
        undelivered += delivery.undelivered(sent, starts)
        fifo += delivery.fifo_violations(sent, starts)
    kept = list(sampler.kept.values()) + (
        [sampler.last] if sampler.last is not None else [])
    ref = np.asarray(grouped_matmul(x, w, sizes))
    err = max((harness.max_scaled_error(np.asarray(o), ref) for o in kept),
              default=float("inf"))

    notes = [f"runs {len(runs)}, images sent {len(msgs)}, "
             f"completed {len(done)}, "
             f"window {window_s:.3f} s, kernel outputs checked {len(kept)}",
             f"set-up: JAX start {t_enter - t0:.3f} s, payload built "
             f"{t_built - t_enter:.3f} s, warm-up batches "
             f"{t_start - t_built:.3f} s",
             "batch wall s: " + " ".join(f"{b:.3f}" for b in batch_s)]
    layer: Dict[str, Any] = {"trace": prof.summary}
    if trace:
        call = flops.gmm_call(E, C, D, D)
        layer.update(
            peaks=harness.peaks(devs[0].device_kind), gmm_call=call,
            time_scale=ts, events=bus.events, live_stats=stats,
            overrun_ms=[((m.done_t - m.start_t) - m.duration) * ts * 1e3
                        for m in done])
    return Outcome(
        attempted=len(msgs),
        failed=undelivered,
        e2e={"images_per_s": len(done) / window_s, "setup_s": setup_s},
        checks=[Check("gmm_max_err", err, cell.limits["gmm_max_err"]),
                Check("undelivered", float(undelivered),
                      cell.limits["undelivered"]),
                Check("fifo_violations", float(fifo),
                      cell.limits["fifo_violations"])],
        memory_peak_bytes=peak,
        layer=layer,
        notes=notes,
    )
