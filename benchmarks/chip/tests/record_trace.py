"""Record the small device trace that ``test_trace_reduce.py`` reads.

Run on a TPU host, from the root of the checkout:

    python3 benchmarks/chip/tests/record_trace.py

It traces a short window of known work -- a jitted bf16 matmul run four
times with a host sleep between calls, and one grouped-matmul Pallas
kernel call -- inside a ``bench.window`` annotation, writes the profiler's
``.xplane.pb`` to ``benchmarks/chip/tests/data/small.xplane.pb``, and
prints every plane and line of it with its event count, so that the
reduction's assumptions about names can be checked by eye.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    from repro.kernels.grouped_matmul.ops import gmm

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda x: (x @ x).astype(jnp.bfloat16))
    x = jnp.ones((2, 256, 512), jnp.float32)
    w = jnp.ones((2, 512, 512), jnp.float32)
    sizes = jnp.full((2,), 256, jnp.int32)
    mm(a).block_until_ready()
    gmm(x, w, sizes).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(4):
                with jax.profiler.TraceAnnotation("bench.matmul"):
                    mm(a).block_until_ready()
                time.sleep(0.005)
            with jax.profiler.TraceAnnotation("bench.gmm"):
                gmm(x, w, sizes).block_until_ready()
        jax.profiler.stop_trace()
        (src,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                           recursive=True)
        dst = HERE / "data" / "small.xplane.pb"
        dst.parent.mkdir(exist_ok=True)
        shutil.copyfile(src, dst)

    prof = jax.profiler.ProfileData.from_file(str(dst))
    for plane in prof.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            seen = set()
            for ev in evs:
                if ev.name in seen or len(seen) >= 12:
                    continue
                seen.add(ev.name)
                print(f"    {ev.name[:90]!r} start={ev.start_ns:.0f} "
                      f"dur={ev.duration_ns:.0f}")
    print(f"wrote {dst} ({dst.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
