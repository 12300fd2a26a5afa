"""Pallas kernel tests: interpret-mode execution vs the pure-jnp oracles.

Every kernel sweeps shapes/dtypes and asserts allclose against its ref.py.
On this CPU container the kernels execute via ``interpret=True`` (the kernel
body runs in Python), which validates the block decomposition, masking, and
online-softmax algebra exactly as it would run on a TPU.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.grouped_matmul.kernel import grouped_matmul
from repro.kernels.grouped_matmul.ops import expert_ffn_swiglu
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref
from repro.kernels.packed_attention.kernel import packed_flash_attention
from repro.kernels.packed_attention.ops import packed_attention
from repro.kernels.packed_attention.ref import packed_attention_ref
from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


def random_packed_segments(rng, B, S, max_segs=4, pad_frac=0.2):
    """Segment ids like the First-Fit packer emits: contiguous, 0-padded."""
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        n_real = int(S * (1 - pad_frac * rng.random()))
        cuts = np.sort(rng.choice(np.arange(1, n_real), size=min(max_segs - 1,
                       n_real - 1), replace=False)) if n_real > 1 else []
        bounds = [0, *cuts, n_real]
        for i in range(len(bounds) - 1):
            seg[b, bounds[i]:bounds[i + 1]] = i + 1
    return seg


def make_qkv(rng, B, S, H, KVH, D, dtype):
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KVH, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, D)), dtype)
    return q, k, v


TOLS = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# packed_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,block", [(256, 128), (512, 256), (384, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_attention_kernel_vs_ref(S, block, dtype):
    rng = np.random.default_rng(0)
    B, H, D = 2, 4, 64
    q, k, v = make_qkv(rng, B, S, H, H, D, dtype)
    seg = jnp.asarray(random_packed_segments(rng, B, S))

    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = packed_flash_attention(
        qt, kt, vt, seg, seg, causal=True,
        block_q=block, block_kv=block, interpret=True,
    )
    ref = packed_attention_ref(qt, kt, vt, seg, seg, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOLS[dtype]
    )


@pytest.mark.parametrize("KVH", [1, 2, 4])
def test_packed_attention_gqa(KVH):
    rng = np.random.default_rng(1)
    B, S, H, D = 1, 256, 4, 32
    q, k, v = make_qkv(rng, B, S, H, KVH, D, jnp.float32)
    seg = jnp.asarray(random_packed_segments(rng, B, S))
    out = packed_attention(q, k, v, seg, seg, interpret=True)
    # oracle with repeated KV heads
    rep = H // KVH
    kf = jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3)
    vf = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3)
    ref = packed_attention_ref(
        q.transpose(0, 2, 1, 3), kf, vf, seg, seg, causal=True
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_packed_attention_sliding_window():
    rng = np.random.default_rng(2)
    B, S, H, D = 1, 256, 2, 32
    q, k, v = make_qkv(rng, B, S, H, H, D, jnp.float32)
    seg = jnp.ones((B, S), jnp.int32)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = packed_flash_attention(
        qt, kt, vt, seg, seg, causal=True, window=64,
        block_q=128, block_kv=128, interpret=True,
    )
    ref = packed_attention_ref(qt, kt, vt, seg, seg, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_packed_attention_fully_padded_rows_are_zero():
    """Rows whose segment id is 0 everywhere must produce zero output."""
    rng = np.random.default_rng(3)
    B, S, H, D = 2, 256, 2, 32
    q, k, v = make_qkv(rng, B, S, H, H, D, jnp.float32)
    seg = jnp.zeros((B, S), jnp.int32).at[0].set(1)  # row 1 fully padded
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = packed_flash_attention(
        qt, kt, vt, seg, seg, causal=True,
        block_q=128, block_kv=128, interpret=True,
    )
    assert jnp.all(out[1] == 0.0)
    assert jnp.all(jnp.isfinite(out))


def test_packed_attention_blocks_never_cross_segments():
    """Attention output for segment A is independent of segment B's content."""
    rng = np.random.default_rng(4)
    B, S, H, D = 1, 256, 2, 32
    q, k, v = make_qkv(rng, B, S, H, H, D, jnp.float32)
    seg = jnp.asarray(
        np.concatenate([np.ones(128, np.int32), np.full(128, 2, np.int32)])
    )[None]
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    call = functools.partial(
        packed_flash_attention, causal=True,
        block_q=128, block_kv=128, interpret=True,
    )
    out1 = call(qt, kt, vt, seg, seg)
    # scramble segment 2's keys/values; segment 1's output must not change
    k2 = kt.at[:, :, 128:].set(jnp.asarray(rng.normal(size=(1, H, 128, D)),
                                           jnp.float32))
    v2 = vt.at[:, :, 128:].set(jnp.asarray(rng.normal(size=(1, H, 128, D)),
                                           jnp.float32))
    out2 = call(qt, k2, v2, seg, seg)
    np.testing.assert_allclose(
        np.asarray(out1[:, :, :128]), np.asarray(out2[:, :, :128]),
        rtol=1e-6, atol=1e-6,
    )


def test_ops_wrapper_matches_model_layout():
    """ops.packed_attention accepts (B, S, H, D) + separate KV heads."""
    rng = np.random.default_rng(5)
    B, S, H, KVH, D = 2, 256, 4, 2, 32
    q, k, v = make_qkv(rng, B, S, H, KVH, D, jnp.float32)
    seg = jnp.asarray(random_packed_segments(rng, B, S))
    out_k = packed_attention(q, k, v, seg, seg, use_kernel=True, interpret=True)
    out_r = packed_attention(q, k, v, seg, seg, use_kernel=False)
    assert out_k.shape == (B, S, H, D)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------


def scatter_pages(rng, lens, num_pages, page_size):
    """Random non-overlapping page assignment (a First-Fit allocator state)."""
    B = len(lens)
    max_pages = max(-(-l // page_size) for l in lens) + 1
    perm = rng.permutation(num_pages)
    pt = np.full((B, max_pages), -1, np.int32)
    off = 0
    for b, l in enumerate(lens):
        n = -(-l // page_size)
        pt[b, :n] = perm[off : off + n]
        off += n
    return pt


@pytest.mark.parametrize("H,KVH", [(8, 2), (4, 4), (16, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel_vs_ref(H, KVH, dtype):
    rng = np.random.default_rng(0)
    B, D = 3, 64
    num_pages, page_size = 48, 16
    lens = [37, 5, 100]
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(num_pages, KVH, page_size, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(num_pages, KVH, page_size, D)), dtype)
    pt = jnp.asarray(scatter_pages(rng, lens, num_pages, page_size))
    sl = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention(q, kp, vp, pt, sl, interpret=True)
    ref = paged_attention_ref(q, kp, vp, pt, sl)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOLS[dtype]
    )


def test_paged_attention_from_allocator():
    """End-to-end with the real First-Fit PageAllocator."""
    from repro.kernels.paged_attention.ops import (
        page_table_from_allocator,
        paged_attention,
    )
    from repro.serving.kv_cache import PageAllocator, PagedCacheLayout

    rng = np.random.default_rng(1)
    KVH, D, page_size = 2, 32, 8
    layout = PagedCacheLayout(num_pages=64, page_size=page_size,
                              n_kv_heads=KVH, head_dim=D,
                              max_pages_per_seq=16)
    alloc = PageAllocator(layout)
    lens = {10: 25, 11: 7, 12: 64}
    for sid, l in lens.items():
        assert alloc.allocate(sid, l) is not None
    alloc.free(11)
    alloc.allocate(13, 30)  # reuses freed low pages (fragmented table)
    seq_ids = [10, 12, 13]

    pt, sl = page_table_from_allocator(alloc, seq_ids)
    B, H = len(seq_ids), 4
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(64, KVH, page_size, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(64, KVH, page_size, D)), jnp.float32)
    out_k = paged_attention(q, kp, vp, pt, sl, use_kernel=True, interpret=True)
    out_r = paged_attention(q, kp, vp, pt, sl, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_ignores_stale_pages():
    """Content of pages not referenced by the table must not matter."""
    rng = np.random.default_rng(2)
    B, H, KVH, D, page_size = 1, 4, 2, 32, 8
    lens = [20]
    kp = jnp.asarray(rng.normal(size=(32, KVH, page_size, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(32, KVH, page_size, D)), jnp.float32)
    pt = jnp.asarray(scatter_pages(rng, lens, 32, page_size))
    sl = jnp.asarray(lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    out1 = paged_decode_attention(q, kp, vp, pt, sl, interpret=True)
    used = set(np.asarray(pt).ravel().tolist()) - {-1}
    unused = [p for p in range(32) if p not in used]
    kp2 = kp.at[jnp.asarray(unused)].set(99.0)
    vp2 = vp.at[jnp.asarray(unused)].set(-99.0)
    out2 = paged_decode_attention(q, kp2, vp2, pt, sl, interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# grouped_matmul
# ---------------------------------------------------------------------------


def packed_input(rng, E, C, d, group_sizes, dtype):
    x = rng.normal(size=(E, C, d))
    valid = np.arange(C)[None, :] < np.asarray(group_sizes)[:, None]
    return jnp.asarray(x * valid[..., None], dtype)


@pytest.mark.parametrize(
    "E,C,d,f,blocks",
    [
        (4, 256, 128, 256, (64, 64, 128)),
        (2, 128, 256, 128, (128, 128, 128)),
        (8, 128, 64, 64, (32, 64, 64)),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_kernel_vs_ref(E, C, d, f, blocks, dtype):
    rng = np.random.default_rng(0)
    gs = jnp.asarray(rng.integers(0, C + 1, size=E), jnp.int32)
    x = packed_input(rng, E, C, d, gs, dtype)
    w = jnp.asarray(rng.normal(size=(E, d, f)), dtype)
    bc, bd, bf = blocks
    out = grouped_matmul(x, w, gs, block_c=bc, block_d=bd, block_f=bf,
                         interpret=True)
    ref = grouped_matmul_ref(x, w, gs)
    tol = dict(rtol=2e-4, atol=2e-4) if dtype == jnp.float32 else dict(
        rtol=5e-2, atol=5e-1)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol
    )


def test_grouped_matmul_empty_bins_cost_nothing_and_zero():
    rng = np.random.default_rng(1)
    E, C, d, f = 4, 128, 64, 64
    gs = jnp.asarray([0, 0, 64, 0], jnp.int32)
    x = packed_input(rng, E, C, d, gs, jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, d, f)), jnp.float32)
    out = grouped_matmul(x, w, gs, block_c=64, block_d=64, block_f=64,
                         interpret=True)
    # empty experts produce exactly zero
    assert np.abs(np.asarray(out)[[0, 1, 3]]).max() == 0.0
    assert np.abs(np.asarray(out)[2, 64:]).max() == 0.0


def test_expert_ffn_swiglu_matches_dense():
    rng = np.random.default_rng(2)
    E, C, d, f = 2, 128, 64, 128
    gs = jnp.asarray([128, 100], jnp.int32)
    x = packed_input(rng, E, C, d, gs, jnp.float32)
    wg = jnp.asarray(rng.normal(size=(E, d, f)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(E, d, f)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(E, f, d)) * 0.1, jnp.float32)
    out = expert_ffn_swiglu(x, wg, wu, wd, gs, use_kernel=True, interpret=True)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, wg)) * jnp.einsum(
        "ecd,edf->ecf", x, wu)
    dense = jnp.einsum("ecf,efd->ecd", h, wd)
    valid = (jnp.arange(C)[None, :] < gs[:, None])[..., None]
    dense = jnp.where(valid, dense, 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-3, atol=2e-3)


def _call_gmm(rng):
    from repro.kernels.grouped_matmul.ops import gmm

    x = jnp.asarray(rng.normal(size=(2, 128, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 128, 128)), jnp.float32)
    return gmm(x, w, jnp.asarray([128, 64], jnp.int32), use_kernel=True)


def _call_paged(rng):
    from repro.kernels.paged_attention.ops import paged_attention

    q = jnp.asarray(rng.normal(size=(1, 4, 32)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(8, 2, 8, 32)), jnp.float32)
    pt = jnp.asarray([[0, 1]], jnp.int32)
    return paged_attention(q, kp, kp, pt, jnp.asarray([12], jnp.int32),
                           use_kernel=True)


def _call_packed(rng):
    q, k, v = make_qkv(rng, 1, 128, 2, 2, 32, jnp.float32)
    seg = jnp.ones((1, 128), jnp.int32)
    return packed_attention(q, k, v, seg, seg, use_kernel=True)


@pytest.mark.parametrize("call", [_call_gmm, _call_paged, _call_packed],
                         ids=["grouped_matmul", "paged", "packed"])
def test_kernel_off_tpu_without_interpret_raises(call):
    """The one dispatch rule: off the TPU a kernel runs only when
    ``interpret=True`` is passed; it never falls back on its own."""
    from repro.kernels.dispatch import KernelBackendError

    assert jax.default_backend() != "tpu"
    with pytest.raises(KernelBackendError, match="needs a TPU"):
        call(np.random.default_rng(0))


def test_kernel_matches_model_flash_attention():
    """The Pallas kernel agrees with the model-side chunked flash attention
    (layers.flash_attention) — the two implementations the system actually
    swaps between."""
    from repro.models.layers import flash_attention

    rng = np.random.default_rng(6)
    B, S, H, KVH, D = 2, 256, 4, 2, 32
    q, k, v = make_qkv(rng, B, S, H, KVH, D, jnp.float32)
    seg = jnp.asarray(random_packed_segments(rng, B, S))
    out_model = flash_attention(q, k, v, seg, seg, causal=True,
                                chunk_q=128, chunk_kv=128)
    out_kernel = packed_attention(q, k, v, seg, seg, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_model), np.asarray(out_kernel), rtol=3e-5, atol=3e-5
    )
