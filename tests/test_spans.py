"""Program spans in the live runtime and named scopes in the model step.

A ``jax.profiler`` trace of a small live run (the jax payload in interpret
mode) holds every span of ``repro.obs.spans.SPANS``, with the ids its
readers join on; the compiled model step names its scopes in its ops'
metadata."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig
from repro.core.sim import SimConfig, WorkerState
from repro.models import DecoderLM, init_params
from repro.obs import spans
from repro.runtime import Master, RuntimeConfig, ScaledClock, SleepPayload, run_live
from repro.scenarios.registry import get_scenario


# the spans of the training input path; a live run opens the others
TRAINING_SPANS = {"repro.data.pack", "repro.train.batch"}


def _host_events(path):
    """``(name, start_ns, end_ns, stats)`` of every ``repro.*`` host event."""
    prof = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced_live_run(tmp_path_factory):
    scn = get_scenario("microscopy")
    cfg = scn.sim_config()
    cfg.t_max = scn.smoke_t_max
    rt = RuntimeConfig(time_scale=0.01, payload="jax", payload_kwargs=dict(
        experts=2, rows=16, dim=128, interpret=True))
    stream = scn.make_stream(0, n_images=8, duration_range=(4.0, 8.0))
    tmp = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(tmp):
        res = run_live(stream, cfg, runtime=rt)
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    return res, _host_events(path)


@pytest.mark.timeout(120)
def test_traced_live_run_emits_every_span(traced_live_run):
    res, events = traced_live_run
    assert res.completed == res.total == 8
    names = {e[0] for e in events}
    assert names == set(spans.SPANS) - TRAINING_SPANS
    count = {n: sum(e[0] == n for e in events) for n in names}
    assert count["repro.live.run"] == count["repro.live.setup"] == 1
    assert count["repro.payload.call"] == count["repro.payload.kernel"] == 8
    (run,) = [e for e in events if e[0] == "repro.live.run"]
    assert all(run[1] <= e[1] and e[2] <= run[2] for e in events)
    boots = [e for e in events if e[0] == "repro.worker.boot"]
    assert boots and all("worker" in e[3] for e in boots)


@pytest.mark.timeout(120)
def test_payload_kernel_lies_inside_its_call(traced_live_run):
    _, events = traced_live_run
    calls = {e[3]["msg_id"]: e for e in events if e[0] == "repro.payload.call"}
    kernels = {e[3]["msg_id"]: e for e in events
               if e[0] == "repro.payload.kernel"}
    assert len(calls) == 8 and set(calls) == set(kernels)
    for msg_id, (_, ks, ke, _) in kernels.items():
        _, cs, ce, _ = calls[msg_id]
        assert cs <= ks < ke <= ce


class _Recorder:
    """Stands in for a span: records when it is entered and left."""

    log = []

    def __init__(self, name, **ids):
        self.key = (name, tuple(sorted(ids.items())))

    def __enter__(self):
        self.log.append(("open", self.key))

    def __exit__(self, *exc):
        self.log.append(("close", self.key))


@pytest.mark.timeout(30)
def test_worker_boot_span_closes_at_promotion(monkeypatch):
    from repro.runtime.lifecycle import Lifecycle
    from repro.runtime.worker import WorkerPool

    monkeypatch.setattr(spans, "span", _Recorder)
    _Recorder.log = []
    cfg = SimConfig(worker_boot_delay=50.0)
    pool = WorkerPool(cfg, Master(), ScaledClock(time_scale=0.005),
                      SleepPayload(), poll_interval=cfg.dt)
    Lifecycle(pool, cfg, pool.clock).scale_workers(3)
    boot = [("repro.worker.boot", (("worker", i),)) for i in range(3)]
    assert _Recorder.log == [("open", k) for k in boot]
    pool.promote_booted(49.0)  # still booting
    pool.kill_worker(2)        # a booting victim's boot ends with it
    assert _Recorder.log[3:] == [("close", boot[2])]
    pool.promote_booted(50.0)
    assert _Recorder.log[4:] == [("close", boot[0]), ("close", boot[1])]
    assert [w.state for w in pool.workers[:2]] == [WorkerState.ACTIVE] * 2


def test_spans_are_shared_null_contexts_without_jax(monkeypatch):
    import sys

    monkeypatch.delitem(sys.modules, "jax.profiler")
    assert spans.span("repro.irm.step") is spans.span("repro.live.run")
    s = spans.open_span("repro.worker.boot", worker=0)
    spans.close_span(s)


def _tiny_lm():
    cfg = ArchConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256)
    model = DecoderLM(cfg)
    params = init_params(model.param_specs(), jax.random.key(0))
    B, S = 2, 8
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "segment_ids": jnp.ones((B, S), jnp.int32),
             "positions": jnp.broadcast_to(jnp.arange(S), (B, S))}
    return model, params, batch


@pytest.mark.timeout(120)
def test_training_input_emits_its_spans(tmp_path):
    """Each batch the packer emits is a ``repro.data.pack`` span with its
    real tokens and slots; each wait of the step loop for the next device
    batch a ``repro.train.batch`` span."""
    from repro.distributed.sharding import make_rules
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import device_batches, host_batches

    model, _, _ = _tiny_lm()
    mesh = make_local_mesh()
    with jax.profiler.trace(str(tmp_path)):
        got = [b["tokens"].shape for _, b in zip(range(3), device_batches(
            host_batches(model.cfg, 32, 4, seed=1), mesh, make_rules(mesh)))]
    assert got == [(4, 32)] * 3
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = _host_events(path)
    assert {e[0] for e in events} == TRAINING_SPANS
    waits = [e for e in events if e[0] == "repro.train.batch"]
    packs = [e[3] for e in events if e[0] == "repro.data.pack"]
    assert len(waits) >= 3 and len(packs) >= 3
    assert all(p["slots"] == 4 * 32 and 0 < p["real_tokens"] <= 4 * 32
               for p in packs)


@pytest.mark.parametrize("program", ["prefill", "decode_step", "train_step"])
def test_model_step_ops_carry_their_scopes(program):
    model, params, batch = _tiny_lm()
    if program == "train_step":
        from repro.training import (OptimizerConfig, init_opt_state,
                                    make_train_step)

        batch = dict(batch, labels=batch["tokens"])
        fn = jax.jit(make_train_step(model, OptimizerConfig()))
        hlo = fn.lower(params, init_opt_state(params), batch).compile(
            ).as_text()
        # autodiff wraps a scope's name: ``transpose(jvp(embed))/...``
        for scope in ("embed", "layers", "attention", "mlp", "norm", "loss",
                      "optimizer"):
            assert re.search(rf"[/(]{scope}[)/]", hlo), scope
        return
    if program == "prefill":
        fn = jax.jit(lambda p, b: model.prefill(p, b, max_len=16))
        args = (params, batch)
    else:
        _, cache = model.prefill(params, batch, max_len=16)
        fn = jax.jit(model.decode_step)
        args = (params, {"tokens": batch["tokens"][:, :1]}, cache)
    hlo = fn.lower(*args).compile().as_text()
    for scope in ("embed/", "layers/", "/attention/", "/mlp/", "/norm/",
                  "logits/"):
        assert scope in hlo, scope
