"""Record the small TPU trace that the trace reader's tests read, and print
the trace's planes, lines and event names.

    python benchmarks/chip/record_fixture.py OUT_DIR

Runs a few tiny jitted programs and one `fdist_matvec` kernel call under
the profiler, with the harness's host annotations around them and one
deliberate host pause, so that the fixture has device ops, a kernel's
custom-call events and an idle gap with a known host activity. Copy
`OUT_DIR/fixture.xplane.pb` to `tests/data/`. Needs a TPU.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    out_dir = os.path.abspath(argv[0])
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_fixture: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels.fdist_matvec.ops import fdist_matvec_batched

    x = jnp.linspace(0.0, 1.0, 2 * 256).reshape(2, 256)
    v = jnp.ones((2, 256, 64), jnp.float32)
    c = jnp.asarray([0.25], jnp.float32)
    kernel = jax.jit(lambda x, v: fdist_matvec_batched(x, x, v, c,
                                                       mode="rational"))
    mm = jax.jit(lambda a: jnp.tanh(a @ a.T).sum(axis=0))
    a = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready((kernel(x, v), mm(a)))  # compile outside
    tdir = os.path.join(out_dir, "raw")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y, z = kernel(x, v), mm(a)
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready((y, z))
        with jax.profiler.TraceAnnotation("bench.prepare"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            y = kernel(x, v)
        with jax.profiler.TraceAnnotation("bench.wait"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    shutil.copy(path, os.path.join(out_dir, "fixture.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            lines.append({"line": line.name, "events": len(evs),
                          "names": names[:25],
                          "first_ns": evs[0].start_ns if evs else None,
                          "last_end_ns": (evs[-1].start_ns
                                          + evs[-1].duration_ns)
                          if evs else None})
        print(json.dumps({"plane": plane.name, "lines": lines}))
    print(json.dumps({"bytes": os.path.getsize(path)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
