"""AST lint for repo invariants the type system can't see.

Rules (suppress a line with a ``# noqa: repro-lint`` comment):

* **frozen-mutation** — no attribute assignment to the frozen ``ITNode`` /
  ``PlanSpec`` dataclasses: ``node.left = ...``, ``spec.pivots = ...`` or
  ``object.__setattr__(...)`` anywhere outside ``plan_api.py`` /
  ``integrator_tree.py`` (the dataclasses' own ``__post_init__`` /
  digest-memo sites).
* **legacy-np-random** — no ``np.random.<fn>()`` module-level legacy API;
  randomness must flow through seeded ``np.random.default_rng`` /
  ``Generator`` objects (or jax PRNG keys).
* **traced-host-read** — inside ``src/repro/{core,kernels,models}``, no
  ``.item()`` and no ``float()/int()/bool()`` wrapped around a ``jnp.``
  expression: forcing a traced value to a python scalar either crashes
  under jit or silently forces a device sync.
* **x64-flip** — no ``jax.config.update("jax_enable_x64", ...)`` (or
  ``enable_x64`` context managers) inside ``src/``; precision policy is
  set by the launcher/tests only.

Pure ``ast`` — no third-party dependencies, so the lint runs anywhere the
repo imports.
"""
from __future__ import annotations

import ast
import dataclasses
from pathlib import Path

# Frozen dataclass field names (ITNode + PlanSpec).  Attribute *writes* to
# these names on a non-self object are flagged; the name sets are disjoint
# enough from mutable-object vocabulary that false positives are rare, and
# noqa covers the rest.
FROZEN_FIELDS = frozenset({
    # ITNode
    "vertex_ids", "depth", "leaf_dists", "pivot", "left", "right",
    "left_ids", "right_ids", "left_d", "right_d", "left_id_d", "right_id_d",
    "left_sorted_ids", "left_seg_starts", "right_sorted_ids",
    "right_seg_starts",
    # PlanSpec
    "pivots", "src_gather", "src_seg", "tgt_gather", "tgt_scatter",
    "children", "root_refs", "job_bucket", "job_row", "leaf_bucket",
    "leaf_row", "path_rows", "path_edges", "cross_piv", "reps", "lcas",
})

LEGACY_NP_RANDOM = frozenset({
    "rand", "randn", "randint", "random", "random_sample", "seed",
    "uniform", "normal", "choice", "permutation", "shuffle", "standard_normal",
    "beta", "binomial", "exponential", "poisson",
})

# files allowed to call object.__setattr__ (frozen-dataclass internals)
SETATTR_ALLOWED = ("plan_api.py", "integrator_tree.py")

# subpackages where host reads of traced values are forbidden
TRACED_SUBPKGS = ("core", "kernels", "models")

NOQA = "noqa: repro-lint"


@dataclasses.dataclass
class LintError:
    path: str
    line: int
    rule: str
    detail: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.detail}"


def _has_jnp(node: ast.AST) -> bool:
    """True if the expression tree references a ``jnp.``/``jax.numpy`` name."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in ("jnp", "lax"):
            return True
        if isinstance(sub, ast.Attribute):
            # jax.numpy..., jax.lax...
            root = sub
            parts = []
            while isinstance(root, ast.Attribute):
                parts.append(root.attr)
                root = root.value
            if isinstance(root, ast.Name) and root.id == "jax" and (
                    "numpy" in parts or "lax" in parts):
                return True
    return False


def _attr_chain(node: ast.AST) -> list[str]:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return parts[::-1]


def check_source(src: str, path: str = "<string>") -> list[LintError]:
    """Lint one python source string; ``path`` controls the per-directory
    rule scoping and appears in the errors."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [LintError(path, e.lineno or 0, "syntax", str(e.msg))]

    lines = src.splitlines()

    def suppressed(lineno: int) -> bool:
        return 0 < lineno <= len(lines) and NOQA in lines[lineno - 1]

    p = Path(path)
    fname = p.name
    in_src = "src" in p.parts and "tests" not in p.parts
    in_traced = in_src and any(sp in p.parts for sp in TRACED_SUBPKGS)
    errors: list[LintError] = []

    def err(node: ast.AST, rule: str, detail: str) -> None:
        if not suppressed(node.lineno):
            errors.append(LintError(path, node.lineno, rule, detail))

    for node in ast.walk(tree):
        # --- frozen-mutation: obj.field = ... on frozen field names ---
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            for t in targets:
                if (isinstance(t, ast.Attribute)
                        and t.attr in FROZEN_FIELDS
                        and isinstance(t.value, ast.Name)
                        and t.value.id != "self"):
                    err(t, "frozen-mutation",
                        f"assignment to frozen field '{t.value.id}.{t.attr}' "
                        f"(ITNode/PlanSpec are immutable; use dataclasses.replace)")

        # --- calls ---
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)

            # object.__setattr__(spec, "field", ...) outside allowed files
            if chain[-2:] == ["object", "__setattr__"] or chain == ["object", "__setattr__"]:
                if fname not in SETATTR_ALLOWED:
                    err(node, "frozen-mutation",
                        "object.__setattr__ bypasses frozen dataclasses "
                        f"(only {SETATTR_ALLOWED} may)")

            # np.random.<legacy>() — any file
            if (len(chain) >= 3 and chain[0] in ("np", "numpy")
                    and chain[1] == "random" and chain[2] in LEGACY_NP_RANDOM):
                err(node, "legacy-np-random",
                    f"legacy global-state API np.random.{chain[2]}; use a "
                    f"seeded np.random.default_rng(...) Generator")

            if in_traced:
                # .item() anywhere in the traced subpackages
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item"):
                    err(node, "traced-host-read",
                        ".item() forces a host sync / fails under jit")
                # float(/int(/bool( around a jnp expression
                if (isinstance(node.func, ast.Name)
                        and node.func.id in ("float", "int", "bool")
                        and node.args and _has_jnp(node.args[0])):
                    err(node, "traced-host-read",
                        f"{node.func.id}() on a jax expression fails under "
                        f"jit; keep it an array or mark static")

            # jax.config.update("jax_enable_x64", ...) inside src/
            if in_src:
                is_cfg = (chain[-2:] == ["config", "update"]
                          and (len(chain) < 3 or chain[0] == "jax"))
                if is_cfg and node.args:
                    a0 = node.args[0]
                    if (isinstance(a0, ast.Constant)
                            and a0.value == "jax_enable_x64"):
                        err(node, "x64-flip",
                            "jax_enable_x64 flip inside src/ changes global "
                            "precision for every caller; tests only")

        # with jax.enable_x64(...): inside src/
        if in_src and isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                ctx = item.context_expr
                if isinstance(ctx, ast.Call):
                    ch = _attr_chain(ctx.func)
                    if ch and ch[-1] in ("enable_x64", "disable_x64"):
                        err(node, "x64-flip",
                            f"{ch[-1]}() context inside src/; precision "
                            f"policy belongs to the launcher/tests")

    return errors


def check_paths(paths: list[str | Path]) -> list[LintError]:
    """Lint every ``.py`` under the given files/directories."""
    errors: list[LintError] = []
    for p in paths:
        p = Path(p)
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            try:
                src = f.read_text()
            except OSError as e:
                errors.append(LintError(str(f), 0, "io", str(e)))
                continue
            errors.extend(check_source(src, str(f)))
    return errors
