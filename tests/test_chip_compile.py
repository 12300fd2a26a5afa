"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts (block shapes off the
(8, 128) tiling, too much VMEM, programs that do not fit HBM), so these
tests compile the main path's kernels at the widths ``chip_smoke.py`` runs,
and OLMo-1B's decode step at full width, for one chip of a ``v5e:2x2``
topology.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.

Nothing runs, so these say nothing about results or times.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.grouped_matmul.kernel import grouped_matmul
from repro.kernels.packed_attention.kernel import packed_flash_attention
from repro.kernels.paged_attention.kernel import paged_decode_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "E,C,d,f,dtype",
    [(8, 512, 2048, 2048, jnp.float32),    # the stream payload's kernel
     (8, 256, 2048, 8192, jnp.bfloat16)],  # chip_smoke's kernel phase
    ids=["payload-f32", "olmo-ffn-bf16"],
)
def test_grouped_matmul_compiles(one_chip, E, C, d, f, dtype):
    c = _compile(
        lambda x, w, g: grouped_matmul(x, w, g),
        _sds(one_chip, (E, C, d), dtype), _sds(one_chip, (E, d, f), dtype),
        _sds(one_chip, (E,), jnp.int32),
    )
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_attention_compiles(one_chip, dtype):
    B, H, KVH, D, page, pages, max_pages = 8, 16, 16, 128, 16, 512, 64
    pool = _sds(one_chip, (pages, KVH, page, D), dtype)
    c = _compile(
        lambda q, k, v, pt, sl: paged_decode_attention(q, k, v, pt, sl),
        _sds(one_chip, (B, H, D), dtype), pool, pool,
        _sds(one_chip, (B, max_pages), jnp.int32),
        _sds(one_chip, (B,), jnp.int32),
    )
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_packed_attention_compiles(one_chip, dtype):
    B, H, S, D = 2, 16, 1024, 128
    qkv = _sds(one_chip, (B, H, S, D), dtype)
    seg = _sds(one_chip, (B, S), jnp.int32)
    c = _compile(
        lambda q, k, v, a, b: packed_flash_attention(q, k, v, a, b),
        qkv, qkv, qkv, seg, seg,
    )
    assert "tpu_custom_call" in c.as_text()


def _top_level_instructions(hlo: str):
    """(computation, instruction line) for every instruction outside the
    computations that fusions call."""
    comps = re.split(r"\n(?=\S)", hlo)
    fused = {name for line in hlo.splitlines() if " fusion(" in line
             for name in re.findall(r"calls=%([\w.\-]+)", line)}
    for comp in comps:
        head = re.match(r"(?:ENTRY )?%([\w.\-]+)", comp)
        if head is None or head.group(1) in fused:
            continue
        for line in comp.splitlines()[1:]:
            yield head.group(1), line.strip()


@pytest.mark.parametrize(
    "B,max_len",
    [(8, 32),     # a short cache
     (12, 1279),  # the decode cell: 1024-token prompts, 256 tokens
     (8, 2048)],  # the prefill cell: OLMo-1B's whole context
)
def test_olmo_1b_decode_step_compiles(one_chip, B, max_len):
    """Full-width OLMo-1B decode (f32 weights and cache) fits one chip,
    and the step writes its K/V rows into the donated cache in place: no
    layer's slab is copied out of the stack, and no stack is copied.  Each
    f32 weight is read once, by the matmul that rounds it: no bf16 copy of
    a stacked weight is made before the layers."""
    from repro.configs import get_config
    from repro.models import abstract_params, build_model

    cfg = get_config("olmo-1b")
    model = build_model(cfg)
    put = lambda a: _sds(one_chip, a.shape, a.dtype)  # noqa: E731
    params = jax.tree.map(put, abstract_params(model.param_specs()))
    cache = jax.tree.map(put, jax.eval_shape(
        lambda: model.init_cache(B, max_len, dtype=jnp.float32)))
    tokens = _sds(one_chip, (B, 1), jnp.int32)
    c = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params, {"tokens": tokens}, cache).compile()
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < 16e9
    # the parent of the in-place write held a copy of the stacks: 6.67e9
    # bytes of temporaries at B=12; a rolled layer loop, a bf16 copy of
    # the block weights: 2.148e9
    assert m.temp_size_in_bytes < 0.6e9
    kv_bytes = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(cache["blocks"]))
    assert m.alias_size_in_bytes >= kv_bytes

    slab = (B, max_len, cfg.n_kv_heads, cfg.head_dim_)
    shape = lambda dims: re.escape(  # noqa: E731
        "f32[" + ",".join(map(str, dims)) + "]")
    stack = shape((cfg.n_periods,) + slab) + r"\{[^}]*\} "
    hlo = c.as_text()
    # whole-stack copies, in or out of a fusion
    assert not re.search(stack + r"(copy|dynamic-update-slice)\(", hlo)
    weight_stacks = "|".join(
        re.escape("bf16[" + ",".join(map(str, a.shape)) + "]")
        for a in jax.tree.leaves(params["blocks"]))
    for comp, line in _top_level_instructions(hlo):
        assert not re.search(
            r"= " + shape(slab) + r"\{[^}]*\} fusion\(", line), (comp, line)
        # the whole stack of a weight rounded before the layers
        assert not re.search(
            r"= (" + weight_stacks + r")\{[^}]*\} (convert|fusion)\(",
            line), (comp, line)
