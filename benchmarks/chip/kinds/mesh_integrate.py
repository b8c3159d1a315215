"""Kind `mesh_integrate`: the exact tree-field integrate `ftfi.apply` over
the MST of a triangle mesh, one (n, d) f32 field per call: the paper's
interpolation of vertex normals, where the field holds the normals of the
vertices whose normals are known and zeros elsewhere.

Set-up makes the mesh and its MST (the input, made here from the
configuration), builds the plan with `ftfi.build` in every run (what a user
pays for a new mesh), makes a pool of fields from the seed on the device
(each with its own seeded set of known vertices), and compiles and warms up
the jitted `ftfi.apply`. Each call of the window integrates the next field
of the pool. A sample of the window's outputs, drawn from the seed, is kept
and compared after the window with the float64 reference of
`mesh_integrate_ref`: on every row where the reference gives every row
(`check_rows` "all"), else on rows drawn from the seed.
"""
from __future__ import annotations

import numpy as np

import mesh_integrate_ref as ref
from work import cross_buckets, integrate_floor_bytes


def program_fn(f: dict):
    """A resolved integrand as the program's cordial function."""
    from repro.core import cordial as C

    if f["family"] == "exp":
        return C.Exponential(float(f["lam"]))
    if f["family"] == "rational":
        return C.Rational((1.0,), (1.0, 0.0, float(f["c"])))
    raise ValueError(f"unknown integrand family {f['family']!r}")


def prng_key(seed: int, stream: int):
    """A JAX key for any whole-number seed (beyond 32 bits too)."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make_fields(seed: int, pool: int, normals: np.ndarray,
                known_share: float):
    """`pool` (n, 3) f32 fields, made on the device in one jitted call: the
    normals of a seeded share of the vertices, zero at the others."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key, nrm):
        return tuple(jnp.where(jax.random.bernoulli(
            k, known_share, nrm.shape[:1])[:, None], nrm, 0.0)
            for k in jax.random.split(key, pool))

    return gen(prng_key(seed, 0), jnp.asarray(normals, jnp.float32))


def check_rows(traffic: dict, seed: int, n: int):
    """The rows compared: None for every row, else rows drawn from the
    seed."""
    k = traffic["check_rows"]
    if k == "all":
        return None
    return np.sort(np.random.default_rng([seed, 2]).choice(
        n, min(int(k), n), replace=False))


def exact(tree: dict, f: dict, rows, fields: list) -> list:
    """The float64 reference of each field on `rows` (None: every row)."""
    if rows is None:
        if f["family"] != "exp":
            raise ValueError("only the exp integrand has every row")
        return [ref.exp_integrate(tree, f["lam"], x) for x in fields]
    return ref.integrate_rows(tree, f, rows, fields)


def tree_of(config: dict, memo: dict) -> dict:
    key = ("mesh", config["subdivisions"])
    if key not in memo:
        memo[key] = ref.mesh_tree(config["subdivisions"])
    return memo[key]


class MeshCell:
    def __init__(self, config, traffic, seed, host, memo):
        import jax
        import jax.numpy as jnp
        from repro import ftfi
        from repro.graphs.graph import WeightedTree

        memo = {} if memo is None else memo
        self.config, self.traffic, self.seed = config, traffic, seed
        with host.span("prepare"):
            tree = tree_of(config, memo)
        self.tree = tree
        n = tree["n"]
        if n != config["n"] or tree["normals"].shape[1] != config["d"]:
            raise ValueError("the mesh does not have the configuration's "
                             "n and d")
        key = ("plan", config["subdivisions"], config["leaf_size"],
               config["plan_seed"])
        with host.span("plan_build", timed=True):
            if key not in memo:
                memo[key] = ftfi.build(
                    WeightedTree(n, tree["u"], tree["v"], tree["w"]),
                    leaf_size=config["leaf_size"], seed=config["plan_seed"])
            spec, params = memo[key]
        self.f = ref.resolve(traffic["f"], tree)
        fn = program_fn(self.f)
        backend = traffic["backend"]
        self.engine = ftfi.describe(spec, fn, backend)["cross_engine"]
        with host.span("prepare"):
            self.fields = make_fields(seed, traffic["pool"], tree["normals"],
                                      traffic["known_share"])
            jax.block_until_ready(self.fields)
        entry = jax.jit(lambda p, x: ftfi.apply(spec, p, fn, x,
                                                backend=backend))
        finite = jax.jit(lambda y: jnp.all(jnp.isfinite(y)))
        with host.span("compile", timed=True):
            self.entry = entry.lower(params, self.fields[0]).compile()
            self.finite = finite.lower(self.fields[0]).compile()
        self.params = params
        with host.span("warmup"):
            for x in self.fields:
                jax.block_until_ready(self.finite(self.entry(params, x)))
        # the window's outputs kept for the check: a reservoir sample of
        # `check_calls` calls, drawn from the seed
        self.rng = np.random.default_rng([seed, 1])
        self.kept: list = []
        self.flags: list = []
        self.k = 0
        d = config["d"]
        self.work = {"n": n, "d": d,
                     "floor_bytes": integrate_floor_bytes(n, d),
                     "kernel_buckets": (cross_buckets(spec)
                                        if self.engine.startswith(
                                            "fdist_matvec") else [])}

    def step(self, host) -> bool:
        i = self.k % len(self.fields)
        with host.span("dispatch"):
            y = self.entry(self.params, self.fields[i])
        with host.span("wait"):
            y.block_until_ready()
        self.flags.append(self.finite(y))
        m = self.traffic["check_calls"]
        if len(self.kept) < m:
            self.kept.append((self.k, i, y))
        else:
            j = int(self.rng.integers(0, self.k + 1))
            if j < m:
                self.kept[j] = (self.k, i, y)
        self.k += 1
        return True

    def after_window(self) -> int:
        """Count the calls whose output was not finite (returned: they are
        failed calls), and bring the kept outputs' compared rows and their
        fields to the host."""
        import jax

        self.nonfinite = int(sum(not bool(f) for f in
                                 jax.device_get(self.flags)))
        self.rows = check_rows(self.traffic, self.seed, self.tree["n"])
        sel = slice(None) if self.rows is None else self.rows
        self.got = [(i, np.asarray(y[sel], np.float64))
                    for _, i, y in self.kept]
        used = sorted({i for _, i, _ in self.kept})
        self.X = {i: np.asarray(self.fields[i], np.float64) for i in used}
        return self.nonfinite

    def free(self):
        del self.entry, self.finite, self.params, self.fields, self.kept
        del self.flags

    def check(self, limits: dict) -> dict:
        """The kept outputs' compared rows against the float64 reference:
        the numbers the limits name."""
        want = exact(self.tree, self.f, self.rows,
                     [self.X[i] for i, _ in self.got])
        err = np.stack([y - r for (_, y), r in zip(self.got, want)])
        nums = ref.compare(err, np.stack(want))
        nums["nonfinite_calls"] = float(self.nonfinite)
        return {k: {"value": nums[k], "limit": v} for k, v in limits.items()}


def setup(config, traffic, seed, host, memo=None):
    return MeshCell(config, traffic, seed, host, memo)


def control(config, traffic, seed, memo=None) -> dict:
    """The numbers `correct` compares, read off the control (the reference
    at float32 `high` in the program's place) on the fields a run with this
    seed makes: on its compared rows, or where it compares every row, on
    `control_rows` rows drawn from the seed."""
    import jax

    memo = {} if memo is None else memo
    tree = tree_of(config, memo)
    n = tree["n"]
    f = ref.resolve(traffic["f"], tree)
    fields = [np.asarray(x, np.float64) for x in jax.device_get(make_fields(
        seed, traffic["pool"], tree["normals"], traffic["known_share"]))]
    fields = fields[:traffic["check_calls"]]
    rows = check_rows(traffic, seed, n)
    if rows is None:
        rows = np.sort(np.random.default_rng([seed, 3]).choice(
            n, min(int(traffic["control_rows"]), n), replace=False))
        want = [r[rows] for r in exact(tree, f, None, fields)]
    else:
        want = exact(tree, f, rows, fields)
    got = ref.control_rows(tree, f, rows, fields)
    err = np.stack([g - r for g, r in zip(got, want)])
    return ref.compare(err, np.stack(want))
