"""Live streaming runtime: component behavior + end-to-end scenarios.

The end-to-end cases run real asyncio execution in scaled wall-clock time
(a few seconds each); every test carries a ``timeout`` marker so a
deadlocked await fails fast instead of hanging CI.
"""

import asyncio

import pytest

from repro.core.queues import HostRequest
from repro.core.sim import PEState, SimConfig, WorkerState
from repro.runtime import (
    Master,
    RuntimeConfig,
    ScaledClock,
    SleepPayload,
    make_payload,
    run_live,
)
from repro.scenarios.engine import run_scenario, summarize_result
from repro.scenarios.registry import get_scenario
from repro.scenarios.streams import Message

# 1 scenario second = 10 ms wall: fast enough for CI, coarse enough that
# event-loop jitter on a loaded runner stays small relative to the delays
FAST = RuntimeConfig(time_scale=0.01)


# ---------------------------------------------------------------------------
# components
# ---------------------------------------------------------------------------


@pytest.mark.timeout(30)
def test_scaled_clock_maps_virtual_to_wall():
    async def go():
        clock = ScaledClock(time_scale=0.01)
        clock.start()
        await clock.sleep(10.0)  # 10 virtual seconds = 0.1 s wall
        return clock.now()

    elapsed = asyncio.run(go())
    assert 10.0 <= elapsed < 20.0


def test_scaled_clock_rejects_nonpositive_scale():
    with pytest.raises(ValueError):
        ScaledClock(time_scale=0.0)


def test_make_payload_unknown_name():
    with pytest.raises(ValueError, match="unknown payload"):
        make_payload("no-such-payload")


@pytest.mark.timeout(30)
def test_master_global_fifo_and_mix():
    async def go():
        master = Master(total_expected=3)
        a1 = Message(image="a", duration=1.0)
        b1 = Message(image="b", duration=1.0)
        a2 = Message(image="a", duration=1.0)
        for m in (a1, b1, a2):
            master.push_back(m)
        assert master.queue_length() == 3.0
        # first-occurrence order: a before b; counts 2/3 and 1/3
        mix = master.queue_image_mix()
        assert list(mix) == ["a", "b"]
        assert mix["a"] == pytest.approx(2 / 3)
        # global FIFO across images
        assert master.backlog_head(3) == [a1, b1, a2]
        # front re-insert beats older arrivals of the same image
        a0 = Message(image="a", duration=1.0)
        master.push_front(a0)
        assert master.backlog_head(4) == [a0, a1, b1, a2]
        assert master.pull("a") is a0
        assert master.pull("a") is a1
        assert master.pull("b") is b1
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(60)
def test_master_backlog_semantics_match_sim_cluster():
    """Drift guard: the live Master deliberately re-implements the sim's
    backlog structure (per-image FIFO deques + global sequence numbers)
    rather than sharing code with the equivalence-pinned ``core/sim.py``
    hot path — so pin the *semantics* instead: the same randomized
    push-back / push-front / pull sequence must leave both backends with
    identical global-FIFO heads and image mixes at every step."""
    import numpy as np

    from repro.core.irm import IRM
    from repro.core.sim import SimCluster, SimConfig

    async def go():
        rng = np.random.default_rng(7)
        master = Master()
        sim = SimCluster(SimConfig(), IRM())
        images = ["a", "b", "c"]
        for _step in range(300):
            op = rng.integers(0, 3)
            img = images[int(rng.integers(0, len(images)))]
            if op == 0:
                m = Message(image=img, duration=1.0)
                master.push_back(m)
                sim._push_back(m)
            elif op == 1:  # failure requeue: insert(0, m) semantics
                m = Message(image=img, duration=1.0)
                master.push_front(m)
                sim._push_front(m)
            elif master.queue_length() > 0:
                # pull the image of the current global-FIFO head, as an
                # idle PE of that image would
                head_img = master.backlog_head(1)[0].image
                pulled = master.pull(head_img)
                dq = sim._img_queues[head_img]
                _, expect = dq.popleft()
                sim._qlen -= 1
                assert pulled is expect
            assert master.queue_length() == sim.queue_length()
            assert master.queue_image_mix() == sim.queue_image_mix()
            assert master.backlog_head(8) == sim.backlog_head(8)
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(30)
def test_master_drain_event_requires_closed_arrivals():
    async def go():
        master = Master(total_expected=1)
        m = Message(image="a", duration=1.0)
        master.push_back(m)
        assert master.pull("a") is m
        m.done_t = 1.0
        master.complete(m)
        assert not master.drained.is_set()  # arrivals still open
        master.close_arrivals()
        assert master.drained.is_set()
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(30)
def test_master_drain_waits_for_in_flight_messages():
    """Regression: with ``total_expected`` unset the completed-count check
    is vacuous, and an empty backlog used to flip ``drained`` while pulled
    messages were still processing at PEs."""

    async def go():
        master = Master()  # total_expected unset (0)
        m = Message(image="a", duration=1.0)
        master.push_back(m)
        assert master.pull("a") is m  # now in flight at a PE
        assert master.in_flight == 1
        master.close_arrivals()
        assert not master.drained.is_set()  # queue empty but work pending
        m.done_t = 1.0
        master.complete(m)
        assert master.in_flight == 0
        assert master.drained.is_set()
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(30)
def test_master_requeue_reinserts_at_head_with_accounting():
    """A failure requeue returns the in-flight message to the global FIFO
    head, clears its start stamp, and keeps the at-least-once counters."""

    async def go():
        master = Master(total_expected=2)
        a1 = Message(image="a", duration=1.0)
        a2 = Message(image="a", duration=1.0)
        master.push_back(a1)
        master.push_back(a2)
        pulled = master.pull("a")
        assert pulled is a1
        pulled.start_t = 5.0
        master.requeue(pulled)  # its worker died
        assert pulled.start_t == -1.0
        assert master.in_flight == 0
        assert master.requeued == 1
        # head re-insert: the requeued message beats the older a2
        assert master.backlog_head(2) == [a1, a2]
        master.close_arrivals()
        assert not master.drained.is_set()  # nothing is done yet
        for _ in range(2):
            m = master.pull("a")
            m.done_t = 1.0
            master.complete(m)
        assert master.drained.is_set()
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(30)
def test_backlog_demand_accumulator_matches_scan():
    """The incremental per-image counters must reproduce the sim's
    64-message head scan exactly — shallow and deep backlogs, after
    interleaved pulls and front requeues."""
    import numpy as np

    from repro.core.irm import IRM, IRMConfig
    from repro.core.sim import SimConfig
    from repro.runtime.live import LiveCluster

    async def go():
        cfg = SimConfig(resource_dims=("cpu", "mem"))
        irm = IRM(IRMConfig())
        master = Master()
        cluster = LiveCluster(cfg, irm, master, pool=None, lifecycle=None)
        est = irm.profiler.estimate

        def scan_demand():
            total = None
            for msg in master.backlog_head(64):
                v = est(msg.image)
                total = v if total is None else total + v
            return total

        rng = np.random.default_rng(3)
        images = ["a", "b", "c", "d"]
        assert cluster.backlog_resource_demand() is None  # empty backlog
        for _step in range(400):
            op = rng.integers(0, 4)
            img = images[int(rng.integers(0, len(images)))]
            if op <= 1:  # bias toward pushes so the backlog exceeds 64
                master.push_back(Message(image=img, duration=1.0))
            elif op == 2:
                master.push_front(Message(image=img, duration=1.0))
            elif master.queue_length() > 0:
                head_img = master.backlog_head(1)[0].image
                master.requeue(master.pull(head_img))
                master.pull(head_img)
            fast, slow = cluster.backlog_resource_demand(), scan_demand()
            assert (fast is None) == (slow is None)
            if fast is not None:
                assert fast.dims == slow.dims
                np.testing.assert_allclose(
                    fast.values, slow.values, rtol=1e-12, atol=1e-12
                )
        assert master.queue_length() > 64  # the deep-backlog path was hit
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(30)
def test_pe_idles_out_and_worker_hosts_while_active():
    """A placed PE starts, drains its queue, then self-terminates."""

    async def go():
        from repro.runtime.lifecycle import Lifecycle
        from repro.runtime.worker import WorkerPool

        cfg = SimConfig(pe_start_delay=0.5, container_idle_timeout=1.0,
                        worker_boot_delay=0.0)
        clock = ScaledClock(time_scale=0.005)
        master = Master(total_expected=1)
        pool = WorkerPool(cfg, master, clock, SleepPayload(),
                          poll_interval=cfg.dt)
        lifecycle = Lifecycle(pool, cfg, clock)
        clock.start()
        lifecycle.scale_workers(1)
        w = pool.workers[0]
        assert w.state is WorkerState.ACTIVE  # zero boot delay
        master.push_back(Message(image="img", duration=2.0))
        assert pool.try_start_pe(
            HostRequest(image="img", size_estimate=0.2, target_worker=0)
        )
        assert w.pes[0].state is PEState.STARTING
        master.close_arrivals()
        await asyncio.wait_for(
            master.drained.wait(), clock.to_wall(60.0)
        )
        assert len(master.completed) == 1
        msg = master.completed[0]
        assert msg.start_t >= 0.5  # start delay elapsed first
        assert msg.done_t == pytest.approx(msg.start_t + 2.0, abs=1.0)
        # the PE idles out and removes itself from its worker
        deadline = clock.now() + 30.0
        while w.pes and clock.now() < deadline:
            await clock.sleep(0.5)
        assert not w.pes
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(30)
def test_try_start_pe_fails_while_worker_boots():
    async def go():
        from repro.runtime.lifecycle import Lifecycle
        from repro.runtime.worker import WorkerPool

        cfg = SimConfig(worker_boot_delay=50.0)
        clock = ScaledClock(time_scale=0.005)
        master = Master()
        pool = WorkerPool(cfg, master, clock, SleepPayload(),
                          poll_interval=cfg.dt)
        lifecycle = Lifecycle(pool, cfg, clock)
        clock.start()
        lifecycle.scale_workers(2)
        assert [w.state for w in pool.workers] == [WorkerState.BOOTING] * 2
        req = HostRequest(image="img", size_estimate=0.2, target_worker=0)
        assert not pool.try_start_pe(req)  # still initializing (paper V-B.2)
        assert not pool.try_start_pe(
            HostRequest(image="img", size_estimate=0.2, target_worker=7)
        )  # out of range
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(30)
def test_lifecycle_defers_scale_down_while_booting():
    """The anti-churn guard: no deactivation while boots are in flight."""

    async def go():
        from repro.runtime.lifecycle import Lifecycle
        from repro.runtime.worker import WorkerPool

        cfg = SimConfig(worker_boot_delay=50.0, max_workers=5)
        clock = ScaledClock(time_scale=0.005)
        pool = WorkerPool(cfg, Master(), clock, SleepPayload(),
                          poll_interval=cfg.dt)
        lifecycle = Lifecycle(pool, cfg, clock)
        clock.start()
        lifecycle.scale_workers(1)   # worker 0 boots, ready at t=50
        pool.promote_booted(50.0)    # its boot completes
        lifecycle.nominal_t = 50.0
        lifecycle.scale_workers(5)   # four more boot, ready at t=100
        lifecycle.scale_workers(2)   # four still BOOTING -> defer scale-down
        assert pool.workers[0].state is WorkerState.ACTIVE
        assert all(
            w.state is WorkerState.BOOTING for w in pool.workers[1:]
        )
        # once everything is ACTIVE the scale-down proceeds, highest first
        pool.promote_booted(100.0)
        lifecycle.nominal_t = 100.0
        lifecycle.scale_workers(2)
        assert [w.state for w in pool.workers] == [
            WorkerState.ACTIVE, WorkerState.ACTIVE, WorkerState.OFF,
            WorkerState.OFF, WorkerState.OFF,
        ]
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(30)
def test_lifecycle_stale_boot_does_not_block_scale_down():
    """Regression: the anti-churn guard is scoped to boots younger than
    ``worker_boot_delay``.  A stale BOOTING slot (its delay already
    elapsed — e.g. orphaned by a failure-driven kill/reboot cycle) must
    not pin the pool at max size forever."""

    async def go():
        from repro.runtime.lifecycle import Lifecycle
        from repro.runtime.worker import WorkerPool

        cfg = SimConfig(worker_boot_delay=5.0, max_workers=5)
        clock = ScaledClock(time_scale=0.001)
        pool = WorkerPool(cfg, Master(), clock, SleepPayload(),
                          poll_interval=cfg.dt)
        lifecycle = Lifecycle(pool, cfg, clock)
        clock.start()
        lifecycle.scale_workers(2)   # workers 0-1 boot, ready at t=5
        pool.promote_booted(5.0)
        lifecycle.nominal_t = 5.0
        lifecycle.scale_workers(3)   # worker 2 boots, ready at t=10
        # a later tick where worker 2 was never promoted (e.g. orphaned
        # by a failure-driven kill/reboot cycle): its ready time is in
        # the past — the stale state the scoped guard must see through
        lifecycle.nominal_t = 20.0
        lifecycle.scale_workers(2)
        assert [w.state for w in pool.workers] == [
            WorkerState.ACTIVE, WorkerState.OFF, WorkerState.BOOTING,
        ]
        # a boot genuinely in flight still defers the scale-down
        lifecycle.scale_workers(3)   # slot 1 reboots, ready at t=25
        lifecycle.scale_workers(2)
        assert pool.workers[1].state is WorkerState.BOOTING
        assert pool.workers[0].state is WorkerState.ACTIVE
        return True

    assert asyncio.run(go())


@pytest.mark.timeout(30)
def test_lifecycle_kill_worker_requeues_in_flight_at_head():
    """The live fault path: the victim's PE tasks are cancelled, their
    in-flight messages re-enter the master queue head (last PE first),
    and the failed slot is never rebooted by later scale-ups."""

    async def go():
        from repro.runtime.lifecycle import Lifecycle
        from repro.runtime.worker import WorkerPool

        cfg = SimConfig(pe_start_delay=0.5, container_idle_timeout=30.0,
                        worker_boot_delay=0.0, max_workers=5)
        clock = ScaledClock(time_scale=0.005)
        master = Master(total_expected=3)
        pool = WorkerPool(cfg, master, clock, SleepPayload(),
                          poll_interval=cfg.dt)
        lifecycle = Lifecycle(pool, cfg, clock)
        clock.start()
        lifecycle.scale_workers(2)
        m1 = Message(image="img", duration=50.0)
        m2 = Message(image="img", duration=50.0)
        m3 = Message(image="img", duration=50.0)
        for m in (m1, m2, m3):
            master.push_back(m)
        for _ in range(2):
            assert pool.try_start_pe(
                HostRequest(image="img", size_estimate=0.2, target_worker=0)
            )
        w = pool.workers[0]
        # let both PEs start and pull their messages
        while not (len(w.pes) == 2 and all(pe.msg for pe in w.pes)):
            await clock.sleep(0.5)
        assert master.in_flight == 2
        tasks = [pe.task for pe in w.pes]
        victims = [pe.msg for pe in w.pes]

        requeued = lifecycle.kill_worker(0)
        assert requeued == 2
        assert w.state is WorkerState.OFF and not w.pes
        assert master.requeued == 2 and master.in_flight == 0
        # insert(0, m) one by one: the last PE's message is globally first
        assert master.backlog_head(3) == [victims[1], victims[0], m3]
        assert all(m.start_t == -1.0 for m in victims)
        await asyncio.gather(*tasks, return_exceptions=True)
        # _pe_main absorbs the CancelledError; done-and-no-complete is the
        # observable contract (the harvested messages never completed)
        assert all(t.done() for t in tasks) and not master.completed
        # killing again is a no-op, and the dead slot is never rebooted
        assert lifecycle.kill_worker(0) == 0
        lifecycle.scale_workers(3)
        assert w.state is WorkerState.OFF
        # fresh slots were appended instead of resurrecting the dead one
        assert len(pool.workers) == 4
        assert all(x.state is not WorkerState.OFF for x in pool.workers[2:])
        return True

    assert asyncio.run(go())


# ---------------------------------------------------------------------------
# end-to-end scenarios on the live backend
# ---------------------------------------------------------------------------


@pytest.mark.timeout(120)
def test_live_completes_synthetic_end_to_end():
    scn = get_scenario("synthetic")
    cfg = scn.sim_config()
    cfg.t_max = scn.smoke_t_max
    res = run_live(
        scn.make_stream(0, **scn.smoke_overrides), cfg, runtime=FAST
    )
    # the threshold predictor may starve a sub-queue_low tail (faithful
    # paper behavior, see the scenario's nearly_completes note)
    assert res.completed >= 0.9 * res.total
    assert res.total == 20
    assert res.target_workers.max() >= 2
    assert (res.scheduled_cpu <= 1.0 + 1e-9).all()
    summary = summarize_result(res, cfg.dt)
    assert summary["mean_busy_utilization"] > 0.1


@pytest.mark.timeout(120)
def test_live_completes_microscopy_end_to_end():
    scn = get_scenario("microscopy")
    cfg = scn.sim_config()
    cfg.t_max = scn.smoke_t_max
    stats = {}
    res = run_live(
        scn.make_stream(0, **scn.smoke_overrides), cfg, runtime=FAST,
        stats=stats,
    )
    assert res.completed == res.total == 40
    assert res.makespan > 0
    # the IRM actually ran and made decisions
    assert stats["ticks"] > 10
    assert stats["irm_step_ms_mean"] > 0
    assert res.pe_count.max() >= 2


@pytest.mark.timeout(120)
def test_live_vector_scenario_respects_rigid_dimensions():
    """microscopy-mem on the live backend: memory is never overcommitted."""
    scn = get_scenario("microscopy-mem")
    cfg = scn.sim_config()
    cfg.t_max = scn.smoke_t_max
    res = run_live(
        scn.make_stream(0, **scn.smoke_overrides), cfg,
        irm_config=scn.irm_config(), runtime=FAST,
    )
    assert res.completed == res.total
    assert res.resource_dims == ("cpu", "mem")
    assert res.measured_res is not None
    d = res.resource_dims.index("mem")
    # rigid dimension: measured memory never exceeds worker capacity
    assert (res.measured_res[:, :, d] <= 1.0 + 1e-9).all()


@pytest.mark.timeout(120)
def test_live_profiler_persists_across_runs():
    """run_scenario(backend='live') reuses one IRM across back-to-back runs."""
    result = run_scenario(
        "microscopy", backend="live", runtime=FAST, n_runs=2,
        stream_overrides=get_scenario("microscopy").smoke_overrides,
        t_max=get_scenario("microscopy").smoke_t_max,
    )
    assert result.backend == "live"
    assert len(result.runs) == 2
    assert all(r.completed == r.total for r in result.runs)


@pytest.mark.timeout(120)
def test_live_jax_payload_runs_real_kernels():
    """The jax payload executes a real kernel per message and still meets
    the calibrated schedule."""
    scn = get_scenario("microscopy")
    cfg = scn.sim_config()
    cfg.t_max = scn.smoke_t_max
    res = run_live(
        scn.make_stream(0, n_images=8, duration_range=(4.0, 8.0)), cfg,
        runtime=RuntimeConfig(time_scale=0.01, payload="jax",
                              payload_kwargs={"interpret": True}),
    )
    assert res.completed == res.total == 8
    # service time = kernel wall time + calibrated padding >= the message's
    # scenario duration (small tolerance: clock/perf_counter jitter)
    for m in res.messages:
        assert m.done_t - m.start_t >= m.duration - 0.5


def test_jax_payload_refuses_cpu_without_interpret():
    """Off the TPU the payload's kernel runs only when interpret mode is
    asked for; it never falls back to the interpreter on its own."""
    from repro.kernels.dispatch import KernelBackendError

    with pytest.raises(KernelBackendError, match="needs a TPU"):
        make_payload("jax")
    assert make_payload("jax", interpret=True).check_error < 1e-4


def test_multiproc_refuses_device_payload(monkeypatch):
    """A device payload is refused by the multiproc transport before the
    payload or any worker process is built."""
    import multiprocessing as mp

    from repro.runtime.payloads import JaxPayload

    def built(*a, **k):
        raise AssertionError("the device payload was constructed")

    monkeypatch.setattr(JaxPayload, "__init__", built)
    scn = get_scenario("microscopy")
    with pytest.raises(ValueError, match="only one process may hold"):
        run_scenario(
            "microscopy", backend="multiproc", n_runs=1,
            runtime=RuntimeConfig(time_scale=0.01, payload="jax",
                                  payload_kwargs={"interpret": True}),
            stream_overrides=scn.smoke_overrides, t_max=scn.smoke_t_max,
        )
    assert mp.active_children() == []


def test_parallel_sweep_refuses_device_payload(monkeypatch):
    from repro.runtime.payloads import JaxPayload
    from repro.scenarios import sweep_policies

    def built(*a, **k):
        raise AssertionError("the device payload was constructed")

    monkeypatch.setattr(JaxPayload, "__init__", built)
    with pytest.raises(ValueError, match="accelerator belongs to one process"):
        sweep_policies(
            "microscopy", ["first-fit", "best-fit"], jobs=2, backend="live",
            runtime=RuntimeConfig(payload="jax",
                                  payload_kwargs={"interpret": True}),
        )


def test_run_scenario_rejects_unknown_backend():
    with pytest.raises(ValueError, match="unknown backend"):
        run_scenario("synthetic", backend="quantum")
    with pytest.raises(ValueError, match="runtime config"):
        run_scenario("synthetic", backend="sim", runtime=RuntimeConfig())
