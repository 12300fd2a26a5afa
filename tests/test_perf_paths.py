"""Tests for the §Perf optimization paths: iterative top-k routing,
group-local MoE dispatch, distributed flash-decode, and the TPU-faithful
HLO accounting (AR+DS ≡ RS, bf16-payload detection)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.launch.hlo_analysis import analyze_hlo_text
from repro.models.layers import _decode_attention_local, decode_attention
from repro.models.moe import _top_k_iterative, expert_capacity

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Iterative top-k (partition-friendly router)
# ---------------------------------------------------------------------------


@given(
    st.integers(min_value=1, max_value=8),
    st.integers(min_value=1, max_value=6),
    st.integers(min_value=0, max_value=2**31 - 1),
)
@settings(max_examples=60, deadline=None)
def test_topk_iterative_matches_lax(T, k, seed):
    E = 16
    k = min(k, E)
    rng = np.random.default_rng(seed)
    probs = jnp.asarray(jax.nn.softmax(
        jnp.asarray(rng.normal(size=(T, E)), jnp.float32)))
    v1, i1 = _top_k_iterative(probs, k)
    v2, i2 = jax.lax.top_k(probs, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
    # indices may differ on exact ties; values define the routing weights
    np.testing.assert_allclose(
        np.sort(np.asarray(i1), axis=-1) == np.sort(np.asarray(i2), axis=-1),
        True,
    )


def test_expert_capacity_alignment():
    # einsum path: 8-aligned (tight); kernel path: 128-aligned (MXU tiles)
    assert expert_capacity(4096, 128, 8, 1.25, align=8) == 320
    assert expert_capacity(4096, 128, 8, 1.25, align=128) == 384
    assert expert_capacity(1, 128, 1, 1.0, align=8) == 8


def test_batch_shard_count_no_mesh():
    from repro.distributed.context import batch_shard_count

    assert batch_shard_count(256) == 1  # no mesh context active


# ---------------------------------------------------------------------------
# Distributed flash-decode
# ---------------------------------------------------------------------------


def test_decode_local_body_matches_dense():
    """offset=0, no collective axes == the dense decode reference."""
    rng = np.random.default_rng(0)
    B, H, KVH, S, D = 3, 8, 2, 64, 32
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    lens = jnp.asarray([5, 64, 33], jnp.int32)
    out_local = _decode_attention_local(q, k, v, lens, 0, (), window=0)
    out_dense = decode_attention(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out_local), np.asarray(out_dense),
                               rtol=2e-5, atol=2e-5)


def test_decode_local_body_offset_masks_correctly():
    """A shard whose slice starts past cache_len contributes nothing."""
    rng = np.random.default_rng(1)
    B, H, KVH, S, D = 2, 4, 2, 32, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    lens = jnp.asarray([10, 20], jnp.int32)
    out = _decode_attention_local(q, k, v, lens, 1000, (), window=0)
    assert np.abs(np.asarray(out)).max() == 0.0


@pytest.mark.slow
def test_distributed_decode_matches_single_device():
    """Run a tiny model's decode under a (2, 4) host-device mesh with the
    sequence-sharded cache + shard_map flash-decode, and compare logits
    against the plain single-device path (subprocess so XLA_FLAGS applies)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed.context import activation_sharding
        from repro.distributed.sharding import (
            batch_shardings, cache_shardings, make_rules, param_shardings)
        from repro.launch.mesh import make_mesh
        from repro.models import build_model, init_params

        cfg = get_config("qwen2-72b").smoke()   # GQA kv < model-axis size
        model = build_model(cfg)
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        B, T = 4, 8
        prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(B, T)),
                             jnp.int32)

        # single-device reference
        cache = model.init_cache(B, 32, dtype=jnp.float32)
        logits_ref = None
        for t in range(T):
            logits_ref, cache = model.decode_step(
                params, {"tokens": prompt[:, t:t+1]}, cache)

        # distributed: (data=2, model=4) mesh, sequence-sharded cache
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(mesh, "serve")
        p_shard = param_shardings(model.param_specs(), mesh, rules)
        params_d = jax.device_put(params, p_shard)
        with mesh, activation_sharding(mesh, rules):
            cache = model.init_cache(B, 32, dtype=jnp.float32)
            c_shard = cache_shardings(cache, mesh, rules)
            cache = jax.device_put(cache, c_shard)
            step = jax.jit(model.decode_step, donate_argnums=(2,))
            logits_d = None
            for t in range(T):
                logits_d, cache = step(
                    params_d, {"tokens": prompt[:, t:t+1]}, cache)
        np.testing.assert_allclose(
            np.asarray(logits_d), np.asarray(logits_ref),
            rtol=2e-3, atol=2e-3)
        print("DISTRIBUTED_DECODE_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DISTRIBUTED_DECODE_OK" in proc.stdout


@pytest.mark.slow
def test_moe_group_local_dispatch_matches_single_device():
    """Group-local MoE dispatch under a mesh == single-device routing
    (same losses within drop-pattern tolerance at zero drops)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed.context import activation_sharding
        from repro.distributed.sharding import (
            batch_shardings, make_rules, param_shardings)
        from repro.launch.mesh import make_mesh
        from repro.models import build_model, init_params, make_batch

        cfg = get_config("qwen3-moe-30b-a3b").smoke()
        model = build_model(cfg)
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
        batch = make_batch(cfg, "train", 8, 64, seed=0)

        loss_ref, _ = model.loss(params, batch)   # G = 1

        mesh = make_mesh((4, 2), ("data", "model"))
        rules = make_rules(mesh, "fsdp")
        p_shard = param_shardings(model.param_specs(), mesh, rules)
        params_d = jax.device_put(params, p_shard)
        with mesh, activation_sharding(mesh, rules):
            loss_d, _ = jax.jit(model.loss)(params_d, batch)  # G = 8
        # same tokens, same experts; only the group partition of capacity
        # differs (zero drops at smoke scale) -> losses match closely
        np.testing.assert_allclose(float(loss_d), float(loss_ref),
                                   rtol=5e-3)
        print("MOE_GROUPS_OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "MOE_GROUPS_OK" in proc.stdout


# ---------------------------------------------------------------------------
# TPU-faithful HLO accounting
# ---------------------------------------------------------------------------


def test_ar_plus_dynamic_slice_counts_as_reduce_scatter():
    hlo = """
HloModule test

%fused_dus (p0: f32[4096], p1: f32[1024]) -> f32[1024] {
  %p0 = f32[4096]{0} parameter(0)
  %p1 = f32[1024]{0} parameter(1)
  ROOT %dynamic-slice.1 = f32[1024]{0} dynamic-slice(%p0), dynamic_slice_sizes={1024}
}

ENTRY %main (p0: f32[4096]) -> f32[1024] {
  %p0 = f32[4096]{0} parameter(0)
  %ar = f32[4096]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %dynamic-slice.0 = f32[1024]{0} dynamic-slice(%ar), dynamic_slice_sizes={1024}
}
"""
    cost = analyze_hlo_text(hlo)
    # RS-equivalent: 1x tensor bytes (16384), not 2x
    assert cost.coll["all-reduce"] == pytest.approx(16384.0)


def test_plain_ar_still_counts_double():
    hlo = """
HloModule test

ENTRY %main (p0: f32[4096]) -> f32[4096] {
  %p0 = f32[4096]{0} parameter(0)
  %ar = f32[4096]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %neg = f32[4096]{0} negate(%ar)
}
"""
    cost = analyze_hlo_text(hlo)
    assert cost.coll["all-reduce"] == pytest.approx(2 * 16384.0)


def test_bf16_payload_detected_behind_cpu_promotion():
    hlo = """
HloModule test

%fused_cc (param_0: f32[1024]) -> f32[1024] {
  %param_0 = f32[1024]{0} parameter(0)
  %convert.1 = bf16[1024]{0} convert(%param_0)
  ROOT %convert.2 = f32[1024]{0} convert(%convert.1)
}

ENTRY %main (p0: f32[1024]) -> f32[4096] {
  %p0 = f32[1024]{0} parameter(0)
  %convert_convert_fusion = f32[1024]{0} fusion(%p0), kind=kLoop, calls=%fused_cc
  ROOT %ag = f32[4096]{0} all-gather(%convert_convert_fusion), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""
    cost = analyze_hlo_text(hlo)
    # payload is semantically bf16: half of the f32 output bytes
    assert cost.coll["all-gather"] == pytest.approx(16384.0 / 2)
