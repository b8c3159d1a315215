"""Record the small TPU trace that the scope reader's tests read, and print
what `scopes` reads from it.

    python benchmarks/chip/record_scope_fixture.py OUT_DIR

A scoped `ftfi.apply` (pallas backend, rational f, as the mesh cell runs
it) on a small random tree, with the `finite` check beside it, run through
the harness's own traced window (`bench.window`, `bench.dispatch`,
`bench.wait`) for a few calls. Copy `OUT_DIR/scopes.xplane.pb` to
`tests/data/`. Needs a TPU.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402

N, LEAF, SECONDS = 3000, 64, 0.01


class Cell:
    """A field integrate and its finite check per call, as `MeshCell`."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        from repro import ftfi
        from repro.core import cordial as C
        from repro.graphs.graph import random_tree

        spec, self.params = ftfi.build(random_tree(N, seed=0),
                                       leaf_size=LEAF)
        fn = C.Rational((1.0,), (1.0, 0.0, 4.0))
        x = jnp.ones((N, 3), jnp.float32)
        self.entry = jax.jit(lambda p, x: ftfi.apply(
            spec, p, fn, x, backend="pallas")).lower(self.params, x).compile()
        self.finite = jax.jit(lambda y: jnp.all(jnp.isfinite(y))).lower(
            x).compile()
        self.x = x
        jax.block_until_ready(self.finite(self.entry(self.params, x)))

    def step(self, host) -> bool:
        with host.span("dispatch"):
            y = self.entry(self.params, self.x)
        with host.span("wait"):
            y.block_until_ready()
        return bool(self.finite(y))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    out_dir = os.path.abspath(argv[0])
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_scope_fixture: needs a TPU", file=sys.stderr)
        return 2
    import scopes
    import trace_reader

    w, path = harness.traced_window(Cell(), SECONDS, harness.Host())
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "scopes.xplane.pb")
    shutil.copy(path, dst)
    data = open(dst, "rb").read()
    parts = scopes.attribute(scopes.scoped_ops(data), scopes.EXEC)
    print(json.dumps({
        "bytes": len(data), "calls": w["calls"],
        "busy_s": trace_reader.summarize(dst)["busy_s"],
        "parts_s": {str(k): v for k, v in (parts or {}).items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
