"""Record the small device trace of the program's spans and scopes that
``test_program_trace.py`` reads.

Run on a TPU host, from the root of the checkout:

    python3 benchmarks/chip/tests/record_spans.py [--out PATH]

Inside a ``bench.window`` annotation it traces a short live run of eight
images, each running the grouped-matmul kernel through the jax payload,
then a tiny decoder's prefill and two decode steps, jitted as the
benchmark's ``lm`` driver jits them (``jit_prefill``, ``jit_step``).  It
writes the profiler's ``.xplane.pb`` to ``PATH`` (default
``benchmarks/chip/tests/data/spans.xplane.pb``) and prints what
``program_trace.py`` reads from it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(HERE / "data" / "spans.xplane.pb"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_spans: needs a TPU", file=sys.stderr)
        return 2
    # the persistent cache's key leaves metadata out: without this, a
    # program compiled from a tree with other scopes could be loaded
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    import program_trace
    from repro.configs.base import ArchConfig
    from repro.models import DecoderLM, init_params
    from repro.runtime import RuntimeConfig, make_payload, run_live
    from repro.scenarios.registry import get_scenario

    scn = get_scenario("microscopy")
    cfg = scn.sim_config()
    cfg.t_max = scn.smoke_t_max
    rt = RuntimeConfig(time_scale=0.01, payload="jax",
                       payload_kwargs=dict(experts=2, rows=256, dim=512))

    model = DecoderLM(ArchConfig(name="tiny", family="dense", n_layers=2,
                                 d_model=256, n_heads=4, n_kv_heads=4,
                                 d_ff=512, vocab_size=1024))
    params = init_params(model.param_specs(), jax.random.key(0))
    B, S = 4, 64
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "segment_ids": jnp.ones((B, S), jnp.int32),
             "positions": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                           (B, S))}

    def prefill(params, batch):
        logits, cache = model.prefill(params, batch, max_len=2 * S)
        return jnp.argmax(logits, -1).astype(jnp.int32)[:, None], cache

    def step(params, tok, cache):
        logits, cache = model.decode_step(params, {"tokens": tok}, cache)
        return jnp.argmax(logits, -1).astype(jnp.int32)[:, None], cache

    prefill, step = jax.jit(prefill), jax.jit(step, donate_argnums=(2,))

    def serve():
        tok, cache = prefill(params, batch)
        for _ in range(2):
            tok, cache = step(params, tok, cache)
        return tok.block_until_ready()

    serve()  # compile outside the trace
    make_payload(rt.payload, **rt.payload_kwargs)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            res = run_live(scn.make_stream(0, n_images=8,
                                           duration_range=(4.0, 8.0)),
                           cfg, runtime=rt)
            serve()
        jax.profiler.stop_trace()
        (src,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True)
        dst = Path(args.out)
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(src, dst)

    print(f"live run: {res.completed} of {res.total} images")
    print(json.dumps(program_trace.summary(program_trace.reduce_file(
        str(dst))), indent=1))
    print(f"wrote {dst} ({dst.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
