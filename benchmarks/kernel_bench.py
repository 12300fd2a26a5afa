"""Kernel structural benchmark: block counts, no timings.

It runs no kernel; a kernel's time is measured only on a TPU.  What it
counts from the kernels' grid logic is the *structural* work saved by the
bin-packing-aware designs:

  - packed_attention: fraction of (q, kv) tile pairs skipped by the causal
    block-skip, and the FLOPs a dense (non-packed, padded) batch would have
    cost vs the packed batch at equal token throughput;
  - paged_attention: pages touched vs pages a dense cache would scan
    (= occupancy of the KV bins);
  - grouped_matmul: capacity blocks skipped at realistic router skew.

Each quantity is an exact block count from the kernels' grid logic.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from repro.data import bimodal_documents, pack_documents, packing_efficiency


def packed_attention_stats(S=4096, block=256) -> Dict[str, float]:
    n = S // block
    total = n * n
    # causal block-skip: tile (iq, ik) runs iff ik*block <= iq*block + block-1
    run = sum(1 for iq in range(n) for ik in range(n) if ik <= iq)
    return {
        "seq_len": S,
        "block": block,
        "causal_block_skip_fraction": 1.0 - run / total,
        "flops_vs_full_rectangle": run / total,
    }


def packing_vs_padding_flops(S=4096, B=8, n_docs=800) -> Dict[str, float]:
    docs = list(bimodal_documents(50000, seed=0, limit=n_docs))
    batches = list(pack_documents(docs, S, B))
    eff = packing_efficiency(batches)
    rows_packed = sum(1 for _ in batches) * B
    rows_padded = len(docs)  # one doc per row
    real_tokens = sum(min(len(d), S) for d in docs)
    # attention FLOPs scale with rows * S^2 (dense causal): padded batches
    # burn rows_padded/rows_packed more matmul work per real token
    return {
        "packing_efficiency": eff,
        "rows_packed": rows_packed,
        "rows_padded_baseline": rows_padded,
        "attention_flops_saved_fraction": 1.0 - rows_packed / rows_padded,
        "real_tokens": real_tokens,
    }


def paged_attention_stats(page_size=16) -> Dict[str, float]:
    rng = np.random.default_rng(0)
    # realistic decode batch: mixed-length sequences in a 32k-slot cache
    lens = rng.integers(64, 32768, size=128)
    max_len = 32768
    pages_touched = int(np.ceil(lens / page_size).sum())
    pages_dense = 128 * (max_len // page_size)
    return {
        "page_size": page_size,
        "pages_touched": pages_touched,
        "pages_dense_scan": pages_dense,
        "kv_read_saved_fraction": 1.0 - pages_touched / pages_dense,
    }


def grouped_matmul_stats(E=128, top_k=8, T=8192, cap_factor=1.25,
                         block_c=128, skew=1.5) -> Dict[str, float]:
    rng = np.random.default_rng(0)
    # Zipf-ish router skew over experts
    w = (1.0 / np.arange(1, E + 1) ** (skew / 4.0))
    w /= w.sum()
    counts = rng.multinomial(T * top_k, w)
    C = max(128, int(np.ceil(T * top_k * cap_factor / E / 128)) * 128)
    blocks_total = E * (C // block_c)
    blocks_run = int(np.minimum(np.ceil(counts / block_c), C // block_c).sum())
    return {
        "experts": E,
        "capacity": C,
        "occupied_block_fraction": blocks_run / blocks_total,
        "gmm_flops_saved_fraction": 1.0 - blocks_run / blocks_total,
        "dropped_fraction": float(
            np.maximum(counts - C, 0).sum() / (T * top_k)
        ),
    }


def run(out_dir: str) -> Dict:
    from .common import dump_json

    summary = {
        "packed_attention": packed_attention_stats(),
        "packing_vs_padding": packing_vs_padding_flops(),
        "paged_attention": paged_attention_stats(),
        "grouped_matmul_qwen3_moe": grouped_matmul_stats(),
    }
    summary["claims"] = {
        "causal_skip_near_half": bool(
            0.4 <= summary["packed_attention"]["causal_block_skip_fraction"]
            <= 0.5
        ),
        "packing_saves_attention_flops": bool(
            summary["packing_vs_padding"]["attention_flops_saved_fraction"]
            > 0.5
        ),
        "paging_saves_kv_reads": bool(
            summary["paged_attention"]["kv_read_saved_fraction"] > 0.3
        ),
    }
    dump_json(out_dir, "kernel_bench.json", summary)
    return summary
