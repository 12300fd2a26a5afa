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


def test_olmo_1b_decode_step_compiles(one_chip):
    """Full-width OLMo-1B decode (f32 weights, batch 8) fits one chip."""
    from repro.configs import get_config
    from repro.models import abstract_params, build_model

    model = build_model(get_config("olmo-1b"))
    put = lambda a: _sds(one_chip, a.shape, a.dtype)  # noqa: E731
    params = jax.tree.map(put, abstract_params(model.param_specs()))
    cache = jax.tree.map(put, jax.eval_shape(
        lambda: model.init_cache(8, 32, dtype=jnp.float32)))
    tokens = _sds(one_chip, (8, 1), jnp.int32)
    c = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params, {"tokens": tokens}, cache).compile()
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < 16e9
