"""Compile a cell's device programs for a described v5e chip, with no chip
attached, and print what each needs in device memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py olmo-1b.decode

For a language-model cell, the prefill and decode programs exactly as its
driver builds them; for a stream cell, the payload's grouped-matmul kernel
at the configuration's shapes.  Each is lowered on shapes placed on one
chip of a described ``v5e:2x2`` topology and compiled by the TPU compiler;
its ``memory_analysis()`` gives argument, output, temporary and aliased
bytes.  A compile that passes here is not a chip run.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def main(names) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models import build_model, pad_vocab

    lm = harness.load_module(HERE / "drivers" / "lm.py")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def report(label, compiled):
        ma = compiled.memory_analysis()
        print(f"  {label}: arguments {ma.argument_size_in_bytes}, "
              f"outputs {ma.output_size_in_bytes}, "
              f"temporaries {ma.temp_size_in_bytes}, "
              f"aliased {ma.alias_size_in_bytes}")

    for name in names:
        cell = harness.find_cell(name)
        cfg, tr = cell.config, cell.traffic
        if cfg["driver"] == "stream":
            # the kernel itself: its wrapper asks the (CPU) backend first
            from repro.kernels.grouped_matmul.kernel import grouped_matmul

            pk = cfg["payload"]
            E, C, D = pk["experts"], pk["rows"], pk["dim"]
            f32 = jnp.float32
            args = (jax.ShapeDtypeStruct((E, C, D), f32, sharding=one),
                    jax.ShapeDtypeStruct((E, D, D), f32, sharding=one),
                    jax.ShapeDtypeStruct((E,), jnp.int32, sharding=one))
            print(f"{name}: grouped matmul ({E}, {C}, {D}) x ({E}, {D}, {D})")
            report("kernel", grouped_matmul.lower(*args, interpret=False)
                   .compile())
            continue
        m = cfg["model"]
        model = build_model(lm.arch(cfg))
        B, P, G = tr["batch"], tr["prompt_len"], tr["gen_tokens"]
        prefill, step = lm.serving_programs(model, P + G - 1)
        shape = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=one)
        weights = jax.eval_shape(
            lambda: lm.make_weights(cfg, 0, pad_vocab(m["vocab_size"])))
        params = jax.tree.map(shape, lm.program_params(model, weights))
        batch = jax.tree.map(shape, jax.eval_shape(
            lambda: lm.prompt_batch(jnp.zeros((B, P), jnp.int32))))
        tok, cache = jax.eval_shape(prefill, params, batch)
        tok, cache = jax.tree.map(shape, (tok, cache))
        weight_bytes = sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(params))
        cache_bytes = sum(a.size * a.dtype.itemsize
                          for a in jax.tree.leaves(cache))
        print(f"{name}: B={B} prompt={P} gen={G}: weights {weight_bytes} B, "
              f"cache {cache_bytes} B")
        report("prefill", prefill.lower(params, batch).compile())
        report("decode", step.lower(params, tok, cache).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or [w["name"] for w in
                                    harness.manifest()["workloads"]]))
