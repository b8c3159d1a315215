"""Device time per program scope, read from the traced window's
`.xplane.pb`.

The program names its parts with `jax.named_scope` (the plan executor's
`ftfi.leaf`, `ftfi.gather`, `ftfi.cross`, `ftfi.scatter`, `ftfi.diag`; the
TopoViT step's `vit.attn`, `vit.alg1`, `vit.mlp`, `adamw`, ...). On the
TPU every device op's event metadata in the trace holds JAX's name stack
as its `tf_op` stat, beside a `program_id` stat; `ProfileData` does not
expose metadata stats, so this module reads them from the XSpace protobuf's
wire format itself (no protobuf package).

The ops are those of `trace_reader`: each chip's leaf ops, clipped to the
host annotation `bench.window`. Each op is joined to its `tf_op` by its
name and its program: the `XLA Modules` event that holds the op names the
program (`jit__lambda(<program_id>)`), because two programs of one cell
may hold ops of the same name. An op counts for the innermost scope of a
set that appears in its name stack (`vit.mlp/...`, `jvp(vit.mlp)` or
`transpose(jvp(vit.mlp))`), or for none of them: the rest.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

from trace_reader import (DEVICE_PLANE, WINDOW, _device_ops, _host_spans,
                          op_name)

EXEC = ("ftfi.leaf", "ftfi.gather", "ftfi.cross", "ftfi.scatter")
STEP = ("vit.alg1", "vit.attn", "vit.mlp", "adamw")
MODULES_LINE = "XLA Modules"
PROGRAM = re.compile(r"\((\d+)\)$")

# XSpace.planes; XPlane.name, .event_metadata, .stat_metadata; map entry
# key and value; XEventMetadata.name, .stats; XStatMetadata.name; XStat
# metadata_id and its values
_SPACE_PLANES, _PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 1, 2, 4, 5
_KEY, _VALUE = 1, 2
_EVENT_NAME, _EVENT_STATS = 2, 5
_STAT_MD_NAME = 2
_STAT_ID, _STAT_UINT, _STAT_INT, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7


def _varint(b, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width values
    are skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield field, v
        elif wire == 2:
            size, i = _varint(b, i)
            yield field, b[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")


def _map_entry(b) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(b):
        if f == _KEY:
            key = v
        elif f == _VALUE:
            value = v
    return key, value


def op_metadata(data: bytes) -> dict[str, dict]:
    """{device plane name: {(program_id, op name): tf_op}} of a serialized
    XSpace; an op without a `tf_op` stat is left out."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != _SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == _PLANE_NAME:
                name = bytes(v).decode()
            elif g == _PLANE_EVENT_MD:
                events.append(_map_entry(v)[1])
            elif g == _PLANE_STAT_MD:
                k, md = _map_entry(v)
                stat_names[k] = next((bytes(x).decode() for h, x in
                                      _fields(md) if h == _STAT_MD_NAME), "")
        if not DEVICE_PLANE.match(name):
            continue
        ops = {}
        for md in events:
            ev_name, stats = "", {}
            for g, v in _fields(md):
                if g == _EVENT_NAME:
                    ev_name = bytes(v).decode()
                elif g == _EVENT_STATS:
                    sid, val = None, None
                    for h, x in _fields(v):
                        if h == _STAT_ID:
                            sid = x
                        elif h in (_STAT_UINT, _STAT_INT):
                            val = x
                        elif h == _STAT_STR:
                            val = bytes(x).decode()
                        elif h == _STAT_REF:
                            val = stat_names.get(x, "")
                    stats[stat_names.get(sid, "")] = val
            if "tf_op" in stats:
                ops[(stats.get("program_id"), op_name(ev_name))] = \
                    stats["tf_op"]
        out[name] = ops
    return out


def _programs(plane) -> tuple[list, list]:
    """Start times and (end, program_id) of the plane's program runs."""
    runs = []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for ev in line.events:
                m = PROGRAM.search(ev.name)
                runs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                             int(m.group(1)) if m else None))
    runs.sort()
    return [r[0] for r in runs], [(r[1], r[2]) for r in runs]


def scoped_ops(data: bytes, chips: int = 1) -> list[tuple[str, str, float]]:
    """(op name; tf_op name stack, or "" where the op has none; device
    seconds inside the window) of each leaf op of the first `chips` chips of
    a serialized XSpace."""
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    planes = list(pd.planes)
    meta = op_metadata(data)
    devices = sorted((int(DEVICE_PLANE.match(p.name).group(1)), p)
                     for p in planes if DEVICE_PLANE.match(p.name))
    devices = [p for _, p in devices][:chips]
    win = [s for s in _host_spans(planes) if s[0] == WINDOW]
    t0, t1 = (win[0][1], win[0][2]) if win else (float("-inf"),
                                                 float("inf"))
    out = []
    for plane in devices:
        tf_ops = meta.get(plane.name, {})
        by_name: dict[str, set] = {}
        for (_, name), tf in tf_ops.items():
            by_name.setdefault(name, set()).add(tf)
        starts, runs = _programs(plane)
        for name, s, e in _device_ops(plane):
            s, e = max(s, t0), min(e, t1)
            if e <= s:
                continue
            k = bisect.bisect_right(starts, s) - 1
            prog = runs[k][1] if k >= 0 and s < runs[k][0] else None
            if prog is not None:
                tf = tf_ops.get((prog, name), "")
            else:  # no program run holds it: the name decides, if unique
                names = by_name.get(name, ())
                tf = next(iter(names)) if len(names) == 1 else ""
            out.append((name, tf, (e - s) * 1e-9))
    return out


def _token(scope: str) -> re.Pattern:
    return re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")


def innermost(stack: str, scopes) -> str | None:
    """The scope of `scopes` that appears last (innermost) in a name stack,
    or None. XLA joins the stacks of merged ops with ';': the first is the
    op's own."""
    stack = stack.split(";", 1)[0]
    best, at = None, -1
    for sc in scopes:
        for m in _token(sc).finditer(stack):
            if m.start() > at:
                best, at = sc, m.start()
    return best


def attribute(ops, scopes) -> dict:
    """{scope: seconds} for each of `scopes`, and the rest under None. In
    a program without these scopes every op is in the rest, and each scope
    holds 0."""
    out = dict.fromkeys(scopes, 0.0)
    out[None] = 0.0
    of = {"": None}  # a step's ops repeat their few thousand stacks
    for _, stack, sec in ops:
        if stack not in of:
            of[stack] = innermost(stack, scopes)
        out[of[stack]] += sec
    return out


_memo: dict = {}


def trace_file() -> Path | None:
    """The `.xplane.pb` this run's traced window left (the harness removes
    older ones before it starts the profiler)."""
    import harness

    return max(harness.TRACE_DIR.rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime_ns, default=None)


def split(ctx, scopes) -> dict | None:
    """`attribute` of the cell's traced window, summed over its chips."""
    if not ctx.get("trace"):
        return None
    path = trace_file()
    if path is None:
        return None
    key = (str(path), path.stat().st_mtime_ns, ctx["chips"], tuple(scopes))
    if key not in _memo:
        _memo[key] = attribute(scoped_ops(path.read_bytes(), ctx["chips"]),
                               scopes)
    return _memo[key]


def per_call_ms(ctx, scopes, scope: str | None) -> float | None:
    """Device ms per call of the window in `scope` of `scopes` (None: in
    none of them)."""
    parts = split(ctx, scopes)
    calls = ctx["window"]["calls"]
    if parts is None or not calls:
        return None
    return 1e3 * parts[scope] / calls
