"""Structural jaxpr auditor for FTFI entry points.

Walks the traced :class:`ClosedJaxpr` of an entry point — recursing into
``pjit`` / ``shard_map`` / ``scan`` / ``while`` / ``cond`` / ``custom_vjp``
call equations through their jaxpr-valued params, *not* by string-matching
the pretty-printer — and checks four program invariants against a declared
budget:

* **collective census** — exact counts per collective primitive
  (``all_to_all``, ``psum_scatter``/``reduce_scatter``, ``all_gather``,
  ``psum``, ``ppermute``, ...).  Any collective not named in the budget
  must appear zero times, so a hidden ``all_gather`` on a sharded path is
  a structured finding, not a substring miss.
* **dtype discipline** — no wide dtypes (f64 / c128 / i64 / u64) on any
  equation output or constvar aval, and f32 accumulators under bf16
  inputs on reduction primitives.
* **baked-in-constant audit** — closure-captured arrays above a size
  threshold.  Float consts are gated separately and tightly: a big float
  const is the classic "weights traced as constants" retrace/memory bug,
  while int32/bool plan index arrays are *intended* trace-time constants.
* **host-callback / debug detection** — ``debug_print`` and friends never
  belong on a production path.

The report is a plain dataclass that serializes to JSON for the CI
artifact; ``audit(...)`` raises nothing — gating is the caller's choice.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import numpy as np

import jax
import jax.extend.core  # noqa: F401  (submodule: not loaded by `import jax`)

# Collective primitive names as they appear in jaxprs.  ``psum_scatter``
# is spelled ``reduce_scatter`` by the lowering; budgets may use either.
COLLECTIVE_PRIMS = frozenset({
    "all_gather", "all_gather_invariant", "all_to_all", "psum", "psum2",
    "reduce_scatter", "ppermute", "pgather", "pbroadcast", "pmax", "pmin",
    "pdot", "axis_index",
})
_ALIASES = {"psum_scatter": "reduce_scatter"}

# Reductions that must accumulate in >= fp32 when fed bf16/fp16 inputs.
ACCUM_PRIMS = frozenset({
    "reduce_sum", "cumsum", "cumlogsumexp", "add_any", "scatter-add",
    "dot_general",
})

WIDE_DTYPES = frozenset({"float64", "complex128", "int64", "uint64"})
_LOW_PRECISION = frozenset({"bfloat16", "float16"})

DEFAULT_BUDGET: dict[str, Any] = {
    "collectives": {},              # prim -> exact count; unlisted -> 0
    "allow_dtypes": [],             # extra wide dtypes to tolerate
    "max_float_const_bytes": 1 << 20,   # 1 MiB of float consts
    "max_const_bytes": 64 << 20,        # 64 MiB total (index arrays OK)
    "require_f32_accum": True,
    "allow_callbacks": False,
}


@dataclasses.dataclass
class Finding:
    kind: str      # collective | wide_dtype | bf16_accum | big_const | callback
    where: str     # eqn path, e.g. "pjit/shard_map/scan"
    detail: str

    def __str__(self) -> str:
        return f"[{self.kind}] {self.where}: {self.detail}"


@dataclasses.dataclass
class AuditReport:
    name: str
    collectives: dict[str, int]
    prim_counts: dict[str, int]
    const_bytes: int
    float_const_bytes: int
    biggest_const: dict | None
    findings: list[Finding]

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["ok"] = self.ok
        return d

    def summary(self) -> str:
        status = "ok" if self.ok else f"{len(self.findings)} finding(s)"
        cols = ", ".join(f"{k}={v}" for k, v in sorted(self.collectives.items())) or "none"
        lines = [f"{self.name}: {status}  collectives: {cols}  "
                 f"consts: {self.const_bytes}B ({self.float_const_bytes}B float)"]
        lines += [f"  - {f}" for f in self.findings]
        return "\n".join(lines)


def _as_closed(fn_or_jaxpr, *args, **kwargs):
    if isinstance(fn_or_jaxpr, jax.extend.core.ClosedJaxpr):
        return fn_or_jaxpr
    return jax.make_jaxpr(fn_or_jaxpr, **kwargs)(*args)


def _sub_jaxprs(eqn) -> Iterator[tuple[Any, list]]:
    """Yield (inner Jaxpr, consts) for every jaxpr-valued param of ``eqn``.

    Covers pjit/shard_map (``jaxpr``), scan/while/cond (``jaxpr`` /
    ``cond_jaxpr`` / ``body_jaxpr`` / ``branches``), custom_vjp/jvp
    (``call_jaxpr``/``fun_jaxpr``) and pallas_call — anything whose params
    carry a Jaxpr or ClosedJaxpr, including tuples/lists of them.
    """
    Closed = jax.extend.core.ClosedJaxpr
    Open = jax.extend.core.Jaxpr
    for val in eqn.params.values():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for item in items:
            if isinstance(item, Closed):
                yield item.jaxpr, item.consts
            elif isinstance(item, Open):
                yield item, []
            elif callable(item) and hasattr(item, "call_jaxpr"):
                cj = item.call_jaxpr  # lu.WrappedFun-ish wrappers
                if isinstance(cj, Closed):
                    yield cj.jaxpr, cj.consts


def iter_eqns(jaxpr, path: tuple[str, ...] = ()) -> Iterator[tuple[Any, tuple[str, ...]]]:
    """Depth-first walk of every equation, yielding (eqn, path)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield eqn, path
        for sub, _consts in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, path + (name,))


def _all_consts(closed) -> list[tuple[Any, Any, tuple[str, ...]]]:
    """(const, aval-or-None, path) for top-level and nested consts."""
    out = [(c, v.aval, ()) for c, v in
           zip(closed.consts, closed.jaxpr.constvars)]
    seen: set[int] = set()
    for eqn, path in iter_eqns(closed.jaxpr):
        for sub, consts in _sub_jaxprs(eqn):
            for c, v in zip(consts, sub.constvars):
                if id(c) in seen:
                    continue
                seen.add(id(c))
                out.append((c, v.aval, path + (eqn.primitive.name,)))
    return out


def collective_census(closed) -> dict[str, int]:
    census: dict[str, int] = {}
    for eqn, _path in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            census[name] = census.get(name, 0) + 1
    return census


def _aval_dtype(aval) -> str | None:
    dt = getattr(aval, "dtype", None)
    return None if dt is None else str(dt)


def _const_nbytes(c) -> int:
    try:
        arr = np.asarray(c)
    except Exception:
        return 0
    return int(arr.nbytes)


def audit(fn_or_jaxpr, *args, name: str = "entry",
          budget: dict | None = None, static_argnums=(),
          **make_jaxpr_kwargs) -> AuditReport:
    """Trace ``fn`` on ``args`` (or take a prebuilt ClosedJaxpr) and audit
    it against ``budget`` (missing keys fall back to :data:`DEFAULT_BUDGET`).
    """
    b = dict(DEFAULT_BUDGET)
    b.update(budget or {})
    if static_argnums:
        make_jaxpr_kwargs["static_argnums"] = static_argnums
    closed = _as_closed(fn_or_jaxpr, *args, **make_jaxpr_kwargs)

    findings: list[Finding] = []
    prim_counts: dict[str, int] = {}
    allow_dtypes = set(b.get("allow_dtypes") or ())
    forbidden = WIDE_DTYPES - allow_dtypes

    # --- pass 1: per-equation census + dtype + callback ---
    for eqn, path in iter_eqns(closed.jaxpr):
        pname = eqn.primitive.name
        prim_counts[pname] = prim_counts.get(pname, 0) + 1
        where = "/".join(path + (pname,)) or pname

        if not b["allow_callbacks"] and (
                "callback" in pname or pname.startswith("debug_")):
            findings.append(Finding(
                "callback", where,
                f"host callback / debug primitive '{pname}' in traced program"))

        for ov in eqn.outvars:
            dt = _aval_dtype(getattr(ov, "aval", None))
            if dt in forbidden:
                findings.append(Finding(
                    "wide_dtype", where, f"equation output has dtype {dt}"))
                break  # one finding per eqn is enough

        if b["require_f32_accum"] and pname in ACCUM_PRIMS:
            in_dts = {_aval_dtype(getattr(v, "aval", None))
                      for v in eqn.invars}
            out_dts = {_aval_dtype(getattr(v, "aval", None))
                       for v in eqn.outvars}
            if in_dts & _LOW_PRECISION and out_dts & _LOW_PRECISION:
                acc = eqn.params.get("preferred_element_type")
                if acc is None or str(np.dtype(acc)) in _LOW_PRECISION:
                    findings.append(Finding(
                        "bf16_accum", where,
                        f"{pname} accumulates in {sorted(out_dts & _LOW_PRECISION)} "
                        f"under low-precision inputs (want fp32 accumulator)"))

    # --- pass 2: collective budget diff ---
    census = collective_census(closed)
    declared = {_ALIASES.get(k, k): int(v)
                for k, v in (b.get("collectives") or {}).items()}
    for prim in sorted(set(census) | set(declared)):
        want, got = declared.get(prim, 0), census.get(prim, 0)
        if got != want:
            findings.append(Finding(
                "collective", prim,
                f"{got} occurrence(s) of '{prim}' (budget {want})"))

    # --- pass 3: constvar dtypes + baked-in-constant audit ---
    total = fl_total = 0
    biggest: dict | None = None
    max_fl = int(b["max_float_const_bytes"])
    for c, aval, path in _all_consts(closed):
        where = "/".join(path + ("const",)) or "const"
        dt = _aval_dtype(aval)
        if dt in forbidden:
            findings.append(Finding(
                "wide_dtype", where, f"captured constant traced as {dt}"))
        nb = _const_nbytes(c)
        total += nb
        arr_dt = getattr(np.asarray(c), "dtype", None) if nb else None
        is_float = arr_dt is not None and arr_dt.kind in "fc"
        if is_float:
            fl_total += nb
        if biggest is None or nb > biggest["bytes"]:
            biggest = {"bytes": nb, "dtype": str(arr_dt), "where": where,
                       "shape": list(getattr(np.asarray(c), "shape", ()))}
        if is_float and nb > max_fl:
            findings.append(Finding(
                "big_const", where,
                f"{nb} B {arr_dt} array baked into the trace as a constant "
                f"(budget {max_fl} B) — weights traced as constants?"))
    if total > int(b["max_const_bytes"]):
        findings.append(Finding(
            "big_const", "const",
            f"total captured constants {total} B exceed budget "
            f"{int(b['max_const_bytes'])} B"))

    return AuditReport(name=name, collectives=census,
                       prim_counts=dict(sorted(prim_counts.items())),
                       const_bytes=total, float_const_bytes=fl_total,
                       biggest_const=biggest, findings=findings)


def assert_clean(fn_or_jaxpr, *args, name: str = "entry",
                 budget: dict | None = None, **kw) -> AuditReport:
    """:func:`audit`, raising ``AssertionError`` with the full report on
    any finding — the one-liner tests use."""
    rep = audit(fn_or_jaxpr, *args, name=name, budget=budget, **kw)
    assert rep.ok, rep.summary()
    return rep
