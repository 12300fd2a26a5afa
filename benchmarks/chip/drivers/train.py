"""Driver for a training cell: pretraining steps of a decoder on the mesh
of every local chip, over First-Fit-packed documents.

The timed path is the program's own (``repro.launch.train``): initial
parameters from the seed made in their FSDP shardings
(``init_sharded_params``), ``make_train_step`` jitted with donation
(``jit_train_step``: bfloat16 compute copy, float32 master weights and
Adam moments), batches from ``StreamingPipeline``'s online First-Fit
packer over ``synthetic_documents`` (``host_batches``), placed on the
mesh by ``batch_shardings`` (``device_batches``), all on
``make_local_mesh()`` under ``make_rules``.  The first steps are warm-up;
then whole steps run until the window's time is up, each step's loss read
one step behind its dispatch, so the device never waits on the host's
read.  ``tokens_per_s`` counts the real (non-padding) tokens of the timed
steps over their wall time.

``correct``: the first step the program runs starts from the seed's
initial weights and zero moments, so Adam's first moment after it is
``(1 - b1) * min(1, clip / grad_norm) * g``: the program's own gradient.
That moment and the weights after the step are copied to the host before
the next step donates them.  After the window the initial weights are
made again from the seed, and the plain float32 reference
(``reference/olmo_train.py``) gives the loss and gradient of the same
first packed batch, with the weights sharded as the program's and
gathered a layer at a time, and plain AdamW's first change of the weights
from that gradient (``adamw_first_update``).  The step's reported loss
(relative error), its gradient and its change of the weights (beyond
float32's rounding of the weights), tensor by tensor (each layer's slice
of a stacked weight is a tensor; the largest relative L2 error counts; a
step that leaves the weights as they were reads about 1), must stay under
the cell's limits, and every timed step's loss must be finite.
"""

from __future__ import annotations

import functools
import glob
import os
import time
from typing import Any, Dict, Iterator, List

import numpy as np

import flops_train
import harness
import program_trace
from harness import Check, Outcome, Profile
from reference import olmo_train


def arch(config: Dict[str, Any]):
    from repro.configs.base import ArchConfig

    return ArchConfig(**config["model"])


def opt_config(config: Dict[str, Any]):
    from repro.training import OptimizerConfig

    return OptimizerConfig(**config["optimizer"])


def doc_params(traffic: Dict[str, Any]) -> Dict[str, Any]:
    """``host_batches``'s document parameters."""
    return {"mean_len": traffic["doc_mean_len"],
            "sigma": traffic["doc_sigma"],
            "max_len": traffic["doc_max_len"],
            "vocab": traffic["doc_vocab"]}


def seeds(seed: int):
    """The program's parameter seed (a 31-bit key) and document seed."""
    return (int(harness.rng(seed, 3).integers(2**31 - 1)),
            int(harness.rng(seed, 5).integers(2**63 - 1)))


def doc_lengths(seg: np.ndarray) -> np.ndarray:
    """Lengths of the documents (segments) in packed rows (B, S)."""
    B = seg.shape[0]
    keys = (np.arange(B)[:, None] * (int(seg.max()) + 1) + seg)[seg > 0]
    counts = np.bincount(keys)
    return counts[counts > 0]


def ref_layout(params) -> Dict[str, Any]:
    """The program's parameter tree as the reference's dict of arrays."""
    blk = params["blocks"]["0"]
    return {"embed": params["embed"], **blk["mixer"], **blk["ffn"]}


def ref_config(config: Dict[str, Any]) -> Dict[str, Any]:
    m = config["model"]
    return {"rope_theta": m["rope_theta"], "vocab_size": m["vocab_size"],
            "z_loss": config["z_loss"]}


def sharded_block_fn(mesh, shardings, cfg: Dict[str, Any], fp8: bool = False):
    """The reference's block function over weights held as ``shardings``
    (the reference layout's ``NamedSharding``s): each chip takes its share
    of the block's rows, gathers each layer's weights where they are used,
    and the gradient comes back summed over the chips in the same
    shardings."""
    import jax
    from jax.sharding import PartitionSpec as P

    specs = {k: s.spec for k, s in shardings.items()}
    data = tuple(a for a in mesh.axis_names if a != "model")

    def gather(name, a):
        spec = tuple(specs[name])
        if name != "embed":
            spec = spec[1:]  # a layer's slice of the stacked weight
        for dim, axes in enumerate(spec):
            if axes is not None:
                a = jax.lax.all_gather(a, axes, axis=dim, tiled=True)
        return a

    def block(w, tokens, seg):
        (total, aux), g = jax.value_and_grad(
            olmo_train.objective, has_aux=True)(
                w, tokens, seg, cfg, fp8=fp8, gather=gather)
        return jax.lax.psum((total, aux), data), g

    rows = P(data)
    # the sums are replicated over ``model``, whose ranks hold the same rows
    return jax.jit(jax.shard_map(
        block, mesh=mesh, in_specs=(specs, rows, rows),
        out_specs=((P(), (P(), P(), P())), specs), check_vma=False))


def reference(model, mesh, rules, pseed: int, first: Dict[str, np.ndarray],
              config: Dict[str, Any], fp8: bool = False, w=None):
    """The reference's loss, cross-entropy and gradient of the first batch
    from the seed's initial weights, and those weights (the reference's
    layout, sharded as the program's; ``w`` if given)."""
    from repro.launch.train import init_sharded_params

    if w is None:
        w = ref_layout(init_sharded_params(model, mesh, rules, pseed))
    fn = sharded_block_fn(mesh, {k: a.sharding for k, a in w.items()},
                          ref_config(config), fp8)
    loss, ce, g = olmo_train.loss_and_grad(
        w, first["tokens"], first["segment_ids"], ref_config(config),
        rows_per_block=int(config["reference_rows_per_block"]), fp8=fp8,
        block_fn=fn)
    return loss, ce, g, w


def on_device(host: Dict[str, np.ndarray], like) -> Dict[str, Any]:
    """Host arrays placed as ``like``'s arrays of the same names."""
    import jax

    return {k: jax.device_put(host[k], a.sharding) for k, a in like.items()}


def first_moment_grads(m, grad_norm: float, opt) -> Dict[str, Any]:
    """The gradient of the first step, from Adam's first moment ``m`` after
    it (both in the reference's layout)."""
    scale = np.float32((1.0 - opt.b1) * min(1.0, opt.grad_clip_norm
                                            / max(grad_norm, 1e-9)))
    return {k: a / scale for k, a in m.items()}


def loss_error(loss: float, ref_loss: float) -> float:
    err = abs(loss - ref_loss) / abs(ref_loss)
    return err if np.isfinite(err) else np.inf


@functools.cache
def _sums():
    import jax
    import jax.numpy as jnp

    def sums(prog, ref, slack):
        out = {}
        for k, r in ref.items():
            axes = None if k == "embed" else tuple(range(1, r.ndim))
            d = jnp.abs(prog[k] - r)
            if slack is not None:
                d = jnp.maximum(d - slack[k], 0.0)
            out[k] = (jnp.sum(jnp.square(d), axis=axes),
                      jnp.sum(jnp.square(r), axis=axes))
        return out

    return jax.jit(sums)


def tensor_errors(prog, ref, slack=None) -> Dict[str, float]:
    """Relative L2 error of each tensor of ``prog`` against ``ref`` (the
    reference's layout), each element's difference less its ``slack``
    where one is given; the table and each layer's slice of every stacked
    weight; inf where the program's is not finite or not of the
    reference's shape."""
    import jax

    if any(prog[k].shape != r.shape for k, r in ref.items()):
        return {k: np.inf for k in ref}
    out = {}
    for name, (num, den) in jax.device_get(_sums()(prog, ref, slack)).items():
        labels = [name] if name == "embed" else [
            f"{name}[{i}]" for i in range(num.shape[0])]
        for label, a, b in zip(labels, np.reshape(num, -1),
                               np.reshape(den, -1)):
            err = np.sqrt(a) / max(np.sqrt(b), 1e-30)
            out[label] = float(err) if np.isfinite(err) else np.inf
    return out


@functools.cache
def _change():
    import jax
    import jax.numpy as jnp

    def change(after, w0):
        # float32 rounds each weight after the step to half the gap to
        # its next float at most
        return ({k: a - w0[k] for k, a in after.items()},
                {k: (jnp.nextafter(jnp.abs(a), jnp.inf) - jnp.abs(a)) / 2
                 for k, a in after.items()})

    return jax.jit(change)


def update_errors(after, w0, ref_grads, config) -> Dict[str, float]:
    """Each tensor's change by the first step, ``after - w0``, against
    plain AdamW's change from the reference gradient, beyond float32's
    rounding of the weights after the step (about 1 where the step leaves
    them unchanged).  At a warm-up's first learning rate a change can be a
    few float32 steps of its weight, and the rounding is then most of the
    difference."""
    change, slack = _change()(after, w0)
    return tensor_errors(change, olmo_train.adamw_first_update(
        w0, ref_grads, config["optimizer"]), slack)


class TrainProfile(Profile):
    """``Profile`` that also reads the program's spans and scopes
    (``program_trace``) from the trace before deleting it."""

    def __init__(self, enabled: bool):
        super().__init__(enabled)
        self.program = None

    def stop(self) -> None:
        if not self.enabled:
            return
        import jax

        from trace_reduce import reduce_file

        jax.profiler.stop_trace()
        try:
            (path,) = glob.glob(os.path.join(self._tmp.name, "**",
                                             "*.xplane.pb"), recursive=True)
            self.summary = reduce_file(path, window=self.WINDOW)
            self.program = program_trace.reduce_file(path, self.WINDOW)
        finally:
            self._tmp.cleanup()


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t0: float, devs) -> Outcome:
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distributed.context import activation_sharding
    from repro.distributed.sharding import make_rules
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import (device_batches, host_batches,
                                    init_sharded_params, jit_train_step)
    from repro.models import build_model
    from repro.training import init_opt_state

    # a program loaded from the compile cache brings this tree's scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    t_enter = time.perf_counter()
    cfg, traffic = cell.config, cell.traffic
    m = cfg["model"]
    a = arch(cfg)
    model = build_model(a)
    opt = opt_config(cfg)
    B, S = int(cfg["global_batch"]), int(traffic["seq_len"])
    pseed, dseed = seeds(seed)
    mesh = make_local_mesh()
    rules = make_rules(mesh)

    seen: List[np.ndarray] = []  # each step's document lengths
    first: Dict[str, np.ndarray] = {}

    def hosts() -> Iterator[Dict[str, Any]]:
        for hb in host_batches(a, S, B, seed=dseed, **doc_params(traffic)):
            seen.append(doc_lengths(hb["segment_ids"]))
            if not first:
                first.update(tokens=hb["tokens"].copy(),
                             segment_ids=hb["segment_ids"].copy())
            yield hb

    prof = TrainProfile(trace)
    with mesh, activation_sharding(mesh, rules):
        params = init_sharded_params(model, mesh, rules, pseed)
        opt_state = init_opt_state(params)
        # the step count replicated on the mesh, as the step returns it:
        # the second step then runs the first one's program
        opt_state["step"] = jax.device_put(opt_state["step"],
                                           NamedSharding(mesh, P()))
        step = jit_train_step(model, opt, remat=cfg["remat"])
        batches = device_batches(hosts(), mesh, rules)
        np.asarray(params["embed"][0, 0])
        t_init = time.perf_counter()

        # warm-up; the first step is the one the check reads
        params, opt_state, met = step(params, opt_state, next(batches))
        loss1, gnorm1 = float(met["loss"]), float(met["grad_norm"])
        t_first = time.perf_counter()
        m1 = jax.tree.map(np.asarray, ref_layout(opt_state["m"]))
        p1 = jax.tree.map(np.asarray, ref_layout(params))
        t_copy = time.perf_counter()
        for _ in range(int(traffic["warmup_steps"]) - 1):
            params, opt_state, met = step(params, opt_state, next(batches))
            float(met["loss"])
        n_warm = len(seen)

        prof.start()
        losses: List[float] = []
        ends: List[float] = []
        pending = None
        t_start = time.perf_counter()
        setup_s = t_start - t0
        end = t_start + seconds
        with prof.span(Profile.WINDOW):
            while True:
                with prof.span("bench.step"):
                    params, opt_state, met = step(params, opt_state,
                                                  next(batches))
                    if pending is not None:
                        losses.append(float(pending))
                        ends.append(time.perf_counter())
                pending = met["loss"]
                if time.perf_counter() >= end:
                    break
            with prof.span("bench.step"):
                losses.append(float(pending))
        window_s = time.perf_counter() - t_start
        ends.append(t_start + window_s)
        prof.stop()
        peak = harness.memory_peak_bytes(devs)
        steps = len(losses)
        timed = seen[n_warm:n_warm + steps]
        del params, opt_state, met, pending, batches

        t_ref = time.perf_counter()
        ref_loss, ref_ce, ref_g, w0 = reference(model, mesh, rules, pseed,
                                                first, cfg)
        t_ref = time.perf_counter() - t_ref
        errs = tensor_errors(first_moment_grads(on_device(m1, w0), gnorm1,
                                                opt), ref_g)
        upd = update_errors(on_device(p1, w0), w0, ref_g, cfg)
    worst = max(errs, key=errs.get)
    worst_u = max(upd, key=upd.get)
    loss_err = loss_error(loss1, ref_loss)
    bad = sum(not np.isfinite(x) for x in losses)
    real = sum(int(d.sum()) for d in timed)

    layer: Dict[str, Any] = {"trace": prof.summary, "program": prof.program}
    if trace:
        layer.update(peaks=harness.peaks(devs[0].device_kind),
                     window_s=window_s, n_chips=len(devs),
                     model_flops=sum(flops_train.train_flops(m, d)
                                     for d in timed))
    step_s = np.diff([t_start] + ends)
    fill = real / (steps * B * S)
    return Outcome(
        attempted=steps,
        failed=bad,
        e2e={"tokens_per_s": real / window_s, "setup_s": setup_s},
        checks=[Check("loss_rel_err", loss_err, cell.limits["loss_rel_err"]),
                Check("grad_rel_err", errs[worst],
                      cell.limits["grad_rel_err"]),
                Check("update_rel_err", upd[worst_u],
                      cell.limits["update_rel_err"]),
                Check("nonfinite_losses", float(bad),
                      cell.limits["nonfinite_losses"])],
        memory_peak_bytes=peak,
        layer=layer,
        notes=[f"mesh {dict(mesh.shape)}, {B} rows of {S} a step; "
               f"{steps} timed steps, window {window_s:.3f} s, "
               f"{real} real tokens of {steps * B * S} slots "
               f"({100 * fill:.2f} %), "
               f"{sum(len(d) for d in timed)} documents",
               f"set-up: JAX start {t_enter - t0:.3f} s, parameters "
               f"{t_init - t_enter:.3f} s, first step compiled or loaded and "
               f"run {t_first - t_init:.3f} s, first moment to the host "
               f"{t_copy - t_first:.3f} s, rest of warm-up "
               f"{t_start - t_copy:.3f} s",
               "step wall s: " + " ".join(f"{x:.3f}" for x in step_s),
               "timed losses: " + " ".join(f"{x:.5f}" for x in losses),
               f"first step: loss {loss1!r}, grad norm {gnorm1!r}; "
               f"reference loss {ref_loss!r} (cross-entropy {ref_ce!r}), "
               f"{t_ref:.3f} s",
               f"largest gradient error {errs[worst]!r} in {worst}; "
               f"table {errs['embed']!r}",
               f"largest update error {upd[worst_u]!r} in {worst_u}; "
               f"table {upd['embed']!r}"],
    )
