"""Run the system's main path once on a TPU and check what comes out.

With no arguments, on one chip, in one process that holds the chip:

  stream  the paper's microscopy use case (767 images, one run) through the
          live runtime on the in-process transport, each message running the
          grouped-matmul Pallas kernel on the chip (``--payload jax``);
          every message must complete, and the payload's warm-up output must
          match ``kernels/grouped_matmul/ref.py``;
  model   ``launch/serve.py:run_local`` at full OLMo-1B width (8 requests,
          16-token prompts, 16 greedy decode steps); the prefill logits are
          compared with the same prefill on the host's CPU backend, and
          each decode step's logits with decode_step fed the same tokens
          one at a time from an empty cache;
  kernels grouped matmul, paged attention and packed attention through
          their ``ops.py`` wrappers (``use_kernel=True``) at OLMo-1B widths,
          each compared with its ``ref.py``.

With ``--four-chips``, on a four-chip host, only the sharded paths and what
they are compared with:

  serve4  OLMo-1B decode under the ``serve`` layout on a 1x4 mesh, compared
          with decode on one chip;
  train4  a few OLMo-1B training steps through ``launch/train.py`` on the
          local mesh, parameters and Adam state sharded over ``data`` (in
          f32 they do not fit one chip); the first step's loss and gradient
          norm are compared with the same loss and gradient on one chip,
          and a planted fault (half the batch's labels masked) must move
          both past their limits.

Exits non-zero before any phase when JAX finds no TPU, and when any phase
fails.  On success the last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Usage:
  python3 chip_smoke.py
  python3 chip_smoke.py --four-chips
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Tolerances, as a fraction of max |reference| (scale-normalized max error).
# f32 at "highest" precision on both sides: only summation order differs.
HIGHEST_RTOL = 2e-3
# The served prefill runs at the default TPU precision, which rounds f32
# matmul operands to bf16 (8-bit mantissa) on the MXU.
DEFAULT_RTOL = 5e-2
# bf16 kernel inputs and outputs: one bf16 rounding of the output is 2^-8.
BF16_RTOL = 2e-2
# Step-0 training loss and gradient norm, sharded vs one chip, same bf16
# compute: relative.  Each must lie well below what masking half the
# batch's labels does to it.
LOSS_RTOL = 1e-4
GRAD_NORM_RTOL = 2e-3

STREAM_PAYLOAD = dict(experts=8, rows=512, dim=2048)


def log(msg: str) -> None:
    print(msg, flush=True)


def scaled_error(out, ref) -> float:
    import numpy as np

    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    if out.shape != ref.shape:
        raise AssertionError(f"shape {out.shape} != reference {ref.shape}")
    if not np.all(np.isfinite(out)):
        raise AssertionError("non-finite values in the output")
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def check(name: str, err: float, tol: float) -> None:
    log(f"  {name}: max error {err:.3e} of max |ref| (tolerance {tol:g})")
    if not err <= tol:
        raise AssertionError(f"{name}: error {err:.3e} > {tol:g}")


def device_label() -> str:
    import jax

    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind}"


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_stream(payload_kwargs=STREAM_PAYLOAD, stream_overrides=None,
                 time_scale: float = 0.01) -> None:
    from repro.runtime.live import RuntimeConfig
    from repro.runtime.payloads import GMM_CHECK_RTOL, JaxPayload
    from repro.scenarios import run_scenario

    t0 = time.perf_counter()
    probe = JaxPayload(**payload_kwargs)
    log(f"[{device_label()}] payload warm-up {time.perf_counter() - t0:.2f} s")
    check("payload grouped matmul vs ref", probe.check_error, GMM_CHECK_RTOL)
    del probe
    t0 = time.perf_counter()
    res = run_scenario(
        "microscopy", backend="live", n_runs=1,
        stream_overrides=stream_overrides,
        runtime=RuntimeConfig(time_scale=time_scale, payload="jax",
                              payload_kwargs=dict(payload_kwargs)),
    )
    wall = time.perf_counter() - t0
    s = res.summary
    log(f"[{device_label()}] microscopy: {s['completed']}/{s['total']} "
        f"messages, makespan {s['makespan_s']:.1f} scenario s, "
        f"{wall:.1f} s wall, expectations {res.expectations}")
    if stream_overrides is None and s["total"] != 767:
        raise AssertionError(f"expected the paper's 767 images, got {s['total']}")
    if s["completed"] != s["total"]:
        raise AssertionError(f"{s['completed']} of {s['total']} completed")


def phase_model(arch: str = "olmo-1b", smoke: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import run_local
    from repro.models import build_model

    gen = 16
    out = run_local(argparse.Namespace(arch=arch, smoke=smoke, requests=8,
                                       gen_tokens=gen))
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    toks = out["tokens"]
    if toks.shape != (8, 1 + gen) or toks.min() < 0 \
            or toks.max() >= cfg.vocab_size:
        raise AssertionError(f"generated tokens out of range: {toks.shape}")

    model = build_model(cfg)
    params, batch = out["params"], out["batch"]
    prompt_len = batch["tokens"].shape[1]
    prefill = jax.jit(
        lambda p, b: model.prefill(p, b, max_len=prompt_len)[0])
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(prefill(jax.device_put(params, cpu),
                                 jax.device_put(batch, cpu)))
        hi = np.asarray(prefill(params, batch))
    check("prefill logits at highest precision vs CPU",
          scaled_error(hi, ref), HIGHEST_RTOL)
    check("served prefill logits (default precision) vs CPU",
          scaled_error(out["prefill_logits"], ref), DEFAULT_RTOL)

    # the served decode continued the prefill's cache; the reference feeds
    # the prompt and the served tokens one at a time from an empty cache
    step = jax.jit(model.decode_step, donate_argnums=(2,))
    fed = np.concatenate([np.asarray(batch["tokens"]), toks[:, :gen]], axis=1)
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(8, prompt_len + gen, dtype=jnp.float32)
        tf = []
        for t in range(prompt_len + gen):
            logits, cache = step(params, {"tokens": fed[:, t:t + 1]}, cache)
            if t >= prompt_len:
                tf.append(np.asarray(logits))
    check("served decode logits (default precision) vs teacher-forced",
          scaled_error(out["decode_logits"], np.stack(tf, axis=1)),
          DEFAULT_RTOL)


def phase_kernels(interpret: bool = False, small: bool = False) -> None:
    """The three kernels at OLMo-1B widths: 16 heads of 128, page size 16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.grouped_matmul.ops import gmm
    from repro.kernels.packed_attention.ops import packed_attention
    from repro.kernels.paged_attention.ops import paged_attention

    rng = np.random.default_rng(0)
    bf16 = jnp.bfloat16
    H, D, P = (4, 128, 16) if small else (16, 128, 16)

    # grouped matmul: 8 expert bins of an OLMo-1B FFN (2048 -> 8192), one
    # full, one empty, the rest partly filled
    E, C, d, f = (4, 128, 256, 512) if small else (8, 256, 2048, 8192)
    sizes = jnp.asarray(rng.integers(0, C + 1, size=E).clip(1, C), jnp.int32)
    sizes = sizes.at[0].set(C).at[1].set(0)
    x = jnp.asarray(rng.standard_normal((E, C, d)), bf16)
    w = jnp.asarray(rng.standard_normal((E, d, f)) / np.sqrt(d), bf16)
    out = gmm(x, w, sizes, use_kernel=True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = gmm(x, w, sizes, use_kernel=False)
    check("grouped_matmul vs ref", scaled_error(out, ref), BF16_RTOL)

    # paged decode attention over a scattered First-Fit page table
    B, num_pages = 8, (64 if small else 512)
    lens = rng.integers(1, (num_pages // B) * P, size=B)
    max_pages = int(-(-lens.max() // P))
    perm = rng.permutation(num_pages)
    table = np.full((B, max_pages), -1, np.int32)
    off = 0
    for b, n in enumerate(-(-lens // P)):
        table[b, :n] = perm[off:off + n]
        off += n
    q = jnp.asarray(rng.standard_normal((B, H, D)), bf16)
    kp = jnp.asarray(rng.standard_normal((num_pages, H, P, D)), bf16)
    vp = jnp.asarray(rng.standard_normal((num_pages, H, P, D)), bf16)
    pt, sl = jnp.asarray(table), jnp.asarray(lens, jnp.int32)
    out = paged_attention(q, kp, vp, pt, sl, use_kernel=True,
                          interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention(q, kp, vp, pt, sl, use_kernel=False)
    check("paged_attention vs ref", scaled_error(out, ref), BF16_RTOL)

    # packed causal attention over First-Fit-packed rows
    B, S = 2, (256 if small else 1024)
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(1, S - 64), 3, replace=False))
        for i, (lo, hi) in enumerate(zip([0, *cuts], [*cuts, S - 64])):
            seg[b, lo:hi] = i + 1
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), bf16)
               for _ in range(3))
    seg = jnp.asarray(seg)
    out = packed_attention(q, k, v, seg, seg, use_kernel=True,
                           interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = packed_attention(q, k, v, seg, seg, use_kernel=False)
    check("packed_attention vs ref", scaled_error(out, ref), BF16_RTOL)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def check_sharded(name: str, held, total: int, n: int) -> None:
    """Every one of the ``n`` devices holds a shard, none the whole."""
    log(f"  {name}: bytes per device {held} of {total} in all")
    if len(held) != n or min(held.values()) == 0:
        raise AssertionError(f"{name}: not every device holds a shard")
    if max(held.values()) >= 0.75 * total:
        raise AssertionError(f"{name}: a device holds (nearly) everything")


def phase_serve4(arch: str = "olmo-1b", smoke: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.distributed.context import activation_sharding
    from repro.distributed.sharding import (bytes_by_device, cache_shardings,
                                            make_rules, param_shardings)
    from repro.launch.mesh import make_mesh
    from repro.models import build_model, init_params

    cfg = get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    model = build_model(cfg)
    n = len(jax.devices())
    B, T, max_len = 8, 16, 32
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(B, T)),
                         jnp.int32)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    step = jax.jit(model.decode_step, donate_argnums=(2,))

    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(B, max_len, dtype=jnp.float32)
        for t in range(T):
            ref, cache = step(params, {"tokens": prompt[:, t:t + 1]}, cache)
        ref = np.asarray(ref)

        mesh = make_mesh((1, n), ("data", "model"))
        rules = make_rules(mesh, "serve")
        params_d = jax.device_put(
            params, param_shardings(model.param_specs(), mesh, rules))
        del params
        total = sum(x.nbytes for x in jax.tree.leaves(params_d))
        check_sharded("serve-layout parameters", bytes_by_device(params_d),
                      total, n)
        with mesh, activation_sharding(mesh, rules):
            cache = model.init_cache(B, max_len, dtype=jnp.float32)
            cache = jax.device_put(cache, cache_shardings(cache, mesh, rules))
            t0 = time.perf_counter()
            for t in range(T):
                out, cache = step(params_d, {"tokens": prompt[:, t:t + 1]},
                                  cache)
            out = np.asarray(out)
        log(f"[{device_label()} x{n}] sharded decode of {T} tokens "
            f"(compile included) {time.perf_counter() - t0:.2f} s")
    check("sharded decode logits vs one chip", scaled_error(out, ref),
          HIGHEST_RTOL)


def phase_train4(arch: str = "olmo-1b", smoke: bool = False,
                 seq_len: int = 512, batch: int = 8, steps: int = 4) -> None:
    import jax
    import numpy as np

    from repro.launch.train import (host_batches, parse_args, run_geometry,
                                    train)
    from repro.models import build_model, init_params
    from repro.training.optimizer import global_norm
    from repro.training.train_step import cast_params_for_compute

    argv = ["--arch", arch, "--mesh", "local", "--seq-len", str(seq_len),
            "--batch-size", str(batch), "--steps", str(steps),
            "--ckpt-every", "0"]
    if smoke:
        argv.append("--smoke")
    n = len(jax.devices())
    with tempfile.TemporaryDirectory() as ckpt:
        args = parse_args(argv + ["--ckpt-dir", ckpt])
        # the reference: the same parameters and first batch on one chip,
        # differentiated through the same bf16 compute copy as the step
        cfg, seq_len, batch = run_geometry(args)
        model = build_model(cfg)
        specs = model.param_specs()
        key = jax.random.PRNGKey(0)
        total = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in
                    jax.tree.leaves(jax.eval_shape(
                        lambda k: init_params(specs, k), key)))
        cp = jax.jit(
            lambda k: cast_params_for_compute(init_params(specs, k)))(key)

        @jax.jit
        def loss_and_grad_norm(cp, b):
            loss, grads = jax.value_and_grad(
                lambda q: model.loss(q, b, remat_policy=args.remat)[0])(cp)
            return loss, global_norm(grads)

        first = next(host_batches(cfg, seq_len, batch))
        ref_loss, ref_gn = map(float, loss_and_grad_norm(cp, first))
        # planted fault: the second half of the batch contributes nothing
        half = dict(first, labels=np.where(
            np.arange(batch)[:, None] < batch // 2, first["labels"], -1))
        bad_loss, bad_gn = map(float, loss_and_grad_norm(cp, half))
        del cp
        out = train(args)
    check_sharded("parameters", out["param_bytes_by_device"], total, n)
    losses, gns = out["losses"], out["grad_norms"]
    log(f"[{device_label()} x{n}] losses {losses}, grad norms {gns}; "
        f"one chip: step-0 loss {ref_loss}, grad norm {ref_gn}; "
        f"half batch: loss {bad_loss}, grad norm {bad_gn}")
    if len(losses) != steps or out["restarts"]:
        raise AssertionError(f"{len(losses)} of {steps} steps, "
                             f"{out['restarts']} restarts")
    if not np.all(np.isfinite(losses)):
        raise AssertionError("non-finite training loss")
    for name, got, ref, bad, tol in (
            ("step-0 loss", losses[0], ref_loss, bad_loss, LOSS_RTOL),
            ("step-0 grad norm", gns[0], ref_gn, bad_gn, GRAD_NORM_RTOL)):
        fault = abs(bad - ref) / abs(ref)
        log(f"  {name}: a half-batch fault moves it {fault:.3e} relative")
        if not fault > tol:
            raise AssertionError(f"{name}: the limit {tol:g} would pass a "
                                 f"half-batch fault ({fault:.3e})")
        check(f"{name}, sharded vs one chip", abs(got - ref) / abs(ref), tol)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths, on a four-chip host")
    args = ap.parse_args(argv)

    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        # the model phase compares with the host's CPU backend
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax

    from repro.launch.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()  # before anything compiles
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    log(f"compilation cache: {cache_dir}")

    if args.four_chips:
        phases = [("serve4", phase_serve4), ("train4", phase_train4)]
    else:
        phases = [("stream", phase_stream), ("model", phase_model),
                  ("kernels", phase_kernels)]
    failed = []
    for name, fn in phases:
        log(f"== phase {name}")
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"== phase {name} FAILED after {time.perf_counter() - t0:.1f} s")
        else:
            log(f"== phase {name} passed in {time.perf_counter() - t0:.1f} s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
