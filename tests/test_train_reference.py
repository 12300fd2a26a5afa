"""OLMo-1B's training step against the plain float32 reference of the chip
benchmark (``benchmarks/chip/reference/olmo_train.py``), on the CPU at
smoke size, over First-Fit-packed rows that hold three documents each and
padding; the step under FSDP on four devices against one device; and the
gradient that the chip benchmark reads back from Adam's first moment."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import SequencePacker
from repro.models import build_model, init_params
from repro.training import OptimizerConfig, init_opt_state, make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(REPO, "benchmarks", "chip")
sys.path.insert(0, CHIP)

from reference import olmo_train  # noqa: E402

# both sides in float32 at ``highest`` precision on the CPU: they differ
# only in the order of their sums (online against whole-row softmax, the
# cross-entropy's chunks), about 1e-6 relative
F32_RTOL = 1e-4


def _model():
    # 250 ids: the table is padded to 256 rows, which hold no token
    cfg = dataclasses.replace(get_config("olmo-1b").smoke(), vocab_size=250)
    return cfg, build_model(cfg)


def _packed(seed: int = 0, S: int = 48, B: int = 3):
    """Rows of three documents (14, 13 and 15 tokens) and 6 slots of
    padding, as the First-Fit packer emits them."""
    rng = np.random.default_rng(seed)
    packer = SequencePacker(S, B)
    for _ in range(B):
        for n in (14, 13, 15):
            packer.feed(rng.integers(0, 250, n))
    packer.flush()
    b = packer.pop_batch(pad_final=True)
    assert np.all(b.segment_ids.max(axis=1) == 3)
    assert np.all((b.segment_ids == 0).sum(axis=1) == 6)
    return {"tokens": b.tokens, "labels": b.labels,
            "segment_ids": b.segment_ids, "positions": b.positions}


def _ref_layout(params):
    blk = params["blocks"]["0"]
    return {"embed": params["embed"], **blk["mixer"], **blk["ffn"]}


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def test_loss_and_gradient_match_the_reference():
    cfg, model = _model()
    params = init_params(model.param_specs(), jax.random.PRNGKey(1))
    batch = _packed()
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch, remat_policy=None),
            has_aux=True)(params)
        ref_loss, _, ref = olmo_train.loss_and_grad(
            _ref_layout(params), batch["tokens"], batch["segment_ids"],
            {"rope_theta": cfg.rope_theta, "vocab_size": cfg.vocab_size,
             "z_loss": 1e-4}, rows_per_block=2)
    assert abs(float(loss) - ref_loss) / ref_loss < F32_RTOL
    got = _ref_layout(grads)
    for name, want in ref.items():
        assert _rel_l2(got[name], want) < F32_RTOL, name
    # the padding rows of the table hold no token: no gradient reaches them
    assert np.all(np.asarray(got["embed"])[cfg.vocab_size:] == 0)


def test_a_document_changes_only_its_own_loss_terms():
    cfg, model = _model()
    params = init_params(model.param_specs(), jax.random.PRNGKey(2))
    batch = _packed(seed=3)
    doc2 = batch["segment_ids"] == 2
    others = dict(batch, labels=np.where(doc2, -1, batch["labels"]))
    changed = dict(others, tokens=np.where(
        doc2, (batch["tokens"] + 17) % cfg.vocab_size, batch["tokens"]))
    assert not np.array_equal(changed["tokens"], others["tokens"])
    with jax.default_matmul_precision("highest"):
        a, _ = model.loss(params, others, remat_policy=None)
        b, _ = model.loss(params, changed, remat_policy=None)
    # the other documents' terms see none of document 2: equal to rounding
    assert abs(float(a) - float(b)) <= 1e-6 * abs(float(a))


@pytest.mark.parametrize("clip", [0.05, 1e9])
def test_first_moment_gives_back_the_gradient(clip):
    """After one step from zero moments, the chip benchmark's
    ``first_moment_grads`` recovers the step's own gradient, clipped
    (``clip`` 0.05, under the gradient's norm) or not."""
    sys.path.insert(0, CHIP)
    import harness

    driver = harness.load_module(harness.HERE / "drivers" / "train.py")
    cfg, model = _model()
    params = init_params(model.param_specs(), jax.random.PRNGKey(4))
    batch = _packed(seed=5)
    opt = OptimizerConfig(grad_clip_norm=clip)
    # float32 compute: the step's gradient is then jax.grad's of the loss
    step = jax.jit(make_train_step(model, opt, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        _, state, met = step(params, init_opt_state(params), batch)
        want = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    assert (float(met["grad_norm"]) > clip) == (clip < 1)
    got = driver.first_moment_grads(
        driver.ref_layout(jax.tree.map(np.asarray, state["m"])),
        float(met["grad_norm"]), opt)
    # the moment's float32 product and quotient: a few ulps
    for name, w in _ref_layout(want).items():
        assert _rel_l2(got[name], w) < 1e-5, name



@pytest.mark.parametrize("clip", [0.05, 1e9])
def test_first_step_is_plain_adamw(clip):
    """The program's first step changes the weights by the reference's
    plain AdamW change (``adamw_first_update``) from the same gradient,
    its warm-up learning rate, clipping, bias correction and decay."""
    cfg, model = _model()
    params = init_params(model.param_specs(), jax.random.PRNGKey(6))
    batch = _packed(seed=7)
    opt = OptimizerConfig(learning_rate=4e-4, eps=1e-5, warmup_steps=20,
                          grad_clip_norm=clip)
    step = jax.jit(make_train_step(model, opt, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        after, _, _ = step(params, init_opt_state(params), batch)
        grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    want = olmo_train.adamw_first_update(
        _ref_layout(params), _ref_layout(grads),
        {k: getattr(opt, k) for k in ("learning_rate", "b1", "b2", "eps",
                                      "weight_decay", "grad_clip_norm",
                                      "warmup_steps")})
    got = _ref_layout(after)
    errs = {}
    for name, w0 in _ref_layout(params).items():
        w0 = np.asarray(w0)
        assert np.any(np.asarray(want[name]) != 0), name
        # both sides' weights after the step, rounded to float32 alike
        errs[name] = _rel_l2(np.asarray(got[name]) - w0,
                             (w0 + np.asarray(want[name])) - w0)
    # float32: the two sides differ by the order of their sums, which moves
    # an element of the weights after the step by one ulp of the weight at
    # most, about 1e-5 of a change of lr * 1 (read: 2.4e-5 at most over
    # both clips)
    assert max(errs.values()) < 2e-4, errs


def test_fsdp_step_on_four_devices_equals_one_device():
    """One ``jit_train_step`` from ``init_sharded_params`` on a First-Fit
    batch, on a 4 x 1 (data, model) mesh of virtual CPU devices and on one
    device (a subprocess, so that the device count applies)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        import numpy as np
        from repro.configs import get_config
        from repro.distributed.context import activation_sharding
        from repro.distributed.sharding import make_rules
        from repro.launch.mesh import make_local_mesh, make_mesh
        from repro.launch.train import (device_batches, host_batches,
                                        init_sharded_params, jit_train_step)
        from repro.models import build_model
        from repro.training import OptimizerConfig, init_opt_state

        cfg = get_config("olmo-1b").smoke()
        model = build_model(cfg)
        host = next(host_batches(cfg, 64, 8, seed=3))

        def one_step(mesh):
            rules = make_rules(mesh)
            with mesh, activation_sharding(mesh, rules):
                p = init_sharded_params(model, mesh, rules, seed=5)
                step = jit_train_step(model, OptimizerConfig())
                p, o, met = step(p, init_opt_state(p),
                                 next(device_batches([host], mesh, rules)))
                return (jax.tree.map(np.asarray, (p, o["m"])),
                        float(met["loss"]), float(met["grad_norm"]))

        four = make_local_mesh()
        assert dict(four.shape) == {"data": 4, "model": 1}
        (p4, m4), loss4, gn4 = one_step(four)
        (p1, m1), loss1, gn1 = one_step(
            make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1]))

        def rel(a, b):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            return np.linalg.norm(a - b) / np.linalg.norm(b)

        # the forward of each row is the same bf16 computation on either
        # mesh; only the mean over rows is summed in another order
        assert abs(loss4 - loss1) <= 1e-5 * loss1, (loss4, loss1)
        # the gradient is reduced across the four devices in bfloat16,
        # against one float32-accumulated product rounded to bfloat16
        # once: bf16's rounding, 2**-8 an element
        assert abs(gn4 - gn1) <= 1e-2 * gn1, (gn4, gn1)
        worst = max(rel(a, b) for a, b in zip(jax.tree.leaves(m4),
                                              jax.tree.leaves(m1)))
        assert worst < 1e-2, worst
        # the first update is lr * sign(g) and weight decay: equal
        # wherever the gradient's sign is
        worst = max(rel(a, b) for a, b in zip(jax.tree.leaves(p4),
                                              jax.tree.leaves(p1)))
        assert worst < 1e-4, worst
        print("FSDP_STEP_OK", loss4, loss1, gn4, gn1)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "FSDP_STEP_OK" in proc.stdout
