"""Tests for the divisibility-aware sharding rules and the trip-count-aware
HLO cost analysis that feeds the roofline."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import axes_to_pspec, make_rules
from repro.launch.hlo_analysis import (
    analyze_hlo_text,
    top_collectives,
)


def mesh_16x16():
    return AbstractMesh((16, 16), ("data", "model"))


def mesh_2x16x16():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------


def test_heads_shard_when_divisible():
    mesh = mesh_16x16()
    rules = make_rules(mesh)
    # 64 heads % 16 == 0 -> sharded on model
    spec = axes_to_pspec(("embed", "heads", "head_dim"), (8192, 64, 128),
                         rules, mesh)
    assert spec == P("data", "model", None)


def test_kv_heads_replicate_when_indivisible():
    mesh = mesh_16x16()
    rules = make_rules(mesh)
    # qwen2: 8 kv heads % 16 != 0 -> replicated
    spec = axes_to_pspec(("embed", "kv_heads", "head_dim"), (8192, 8, 128),
                         rules, mesh)
    assert spec == P("data", None, None)


def test_experts_ep_vs_fallback():
    mesh = mesh_16x16()
    rules = make_rules(mesh)
    # qwen3-moe: 128 experts % 16 == 0 -> EP on model
    spec = axes_to_pspec(("experts", "embed", "mlp"), (128, 2048, 768),
                         rules, mesh)
    assert spec == P("model", "data", None)
    # grok: 8 experts % 16 != 0 -> replicate experts, shard d_ff instead
    spec = axes_to_pspec(("experts", "embed", "mlp"), (8, 6144, 32768),
                         rules, mesh)
    assert spec == P(None, "data", "model")


def test_axis_used_once_per_tensor():
    mesh = mesh_16x16()
    rules = make_rules(mesh)
    # vocab wants model, mlp wants model: only the first dim gets it
    spec = axes_to_pspec(("vocab", "mlp"), (65536, 4096), rules, mesh)
    assert spec == P("model", None)


def test_kv_seq_composes_remaining_axes():
    mesh = mesh_16x16()
    rules = make_rules(mesh)
    # decode cache (layers, B, S, KVH, hd): batch over data, kv_seq gets model
    spec = axes_to_pspec(
        ("layers", "batch", "kv_seq", "kv_heads", None),
        (8, 128, 32768, 8, 128), rules, mesh,
    )
    assert spec == P(None, "data", "model", None, None)
    # long_500k: B=1 -> batch unshardable, kv_seq takes data AND model
    spec = axes_to_pspec(
        ("layers", "batch", "kv_seq", "kv_heads", None),
        (4, 1, 524288, 8, 128), rules, mesh,
    )
    assert spec == P(None, None, ("data", "model"), None, None)


def test_multipod_embed_takes_pod_and_data():
    mesh = mesh_2x16x16()
    rules = make_rules(mesh)
    spec = axes_to_pspec(("embed", "mlp"), (8192, 29568), rules, mesh)
    assert spec == P(("pod", "data"), "model")


def test_indivisible_dim_skips_axis_entirely():
    mesh = mesh_16x16()
    rules = make_rules(mesh)
    # internvl2: d_model=896; 896 % 16 == 0 -> shards; 14 heads -> replicated
    spec = axes_to_pspec(("embed", "heads", "head_dim"), (896, 14, 64),
                         rules, mesh)
    assert spec == P("data", None, None)


# ---------------------------------------------------------------------------
# HLO analysis (trip-count-aware cost)
# ---------------------------------------------------------------------------


def test_dot_flops_counted():
    def f(a, b):
        return a @ b

    a = jnp.zeros((128, 256), jnp.float32)
    b = jnp.zeros((256, 64), jnp.float32)
    hlo = jax.jit(f).lower(a, b).compile().as_text()
    cost = analyze_hlo_text(hlo)
    assert cost.flops == pytest.approx(2 * 128 * 256 * 64, rel=0.01)


def test_scan_loop_multiplier():
    """cost_analysis counts a while body once; ours multiplies by trips."""
    TRIPS = 7

    def f(x):
        def body(c, _):
            return c @ c, None
        out, _ = jax.lax.scan(body, x, None, length=TRIPS)
        return out

    x = jnp.zeros((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    hlo = compiled.as_text()
    cost = analyze_hlo_text(hlo)
    expect = TRIPS * 2 * 64 ** 3
    assert cost.flops == pytest.approx(expect, rel=0.05)
    # XLA's own analysis undercounts (body counted once) — this is exactly
    # why hlo_analysis exists; guard the assumption:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    xla_flops = float(ca.get("flops", 0.0))
    assert xla_flops < expect


def test_collective_wire_bytes_conventions():
    hlo = """
HloModule test

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[4096]{0} all-gather(%ar), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %cp = f32[1024]{0} collective-permute(%ar), source_target_pairs={{0,1}}
}
"""
    cost = analyze_hlo_text(hlo)
    assert cost.coll["all-reduce"] == pytest.approx(2 * 4096.0)  # 2x bytes
    assert cost.coll["all-gather"] == pytest.approx(16384.0)     # output bytes
    assert cost.coll["collective-permute"] == pytest.approx(4096.0)
    assert cost.coll_count == 3
    assert cost.dcn_bytes == 0.0


def test_cross_pod_classified_as_dcn():
    hlo = """
HloModule test

ENTRY %main (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  ROOT %ar = f32[256]{0} all-reduce(%p0), replica_groups={{0,256}}, to_apply=%add
}
"""
    cost = analyze_hlo_text(hlo, pod_size=256)
    assert cost.dcn_bytes > 0
    assert cost.ici_bytes == 0.0


def test_real_program_collectives_under_mesh():
    """An actually-sharded program reports nonzero collective bytes."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a real collective")


def test_top_collectives_ranking():
    def f(x):
        def body(c, _):
            return c @ c, None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    hlo = jax.jit(f).lower(jnp.zeros((32, 32))).compile().as_text()
    rows = top_collectives(hlo, n=5)
    assert isinstance(rows, list)  # no collectives on 1 device -> empty ok


def test_peaks_keyed_by_device_kind():
    from repro.launch.analysis import peaks

    assert peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
