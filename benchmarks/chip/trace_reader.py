"""Reduce a profiler trace (`.xplane.pb`) of the measured window to what
the per-layer metrics and the result's `breakdown` read:

- the busy union of each chip's device ops inside the window, averaged
  over the chips used, and the window's length (the host annotation
  `bench.window`);
- the device time of every op name, summed over the chips used;
- the idle gaps of the first chip, each labelled with the innermost
  harness annotation (`bench.*`) that covers its middle on the host: what
  the host was doing while the device waited.
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PREFIX = "bench."
WINDOW = "bench.window"
TOP = 10


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_spans(planes) -> list[tuple[str, float, float]]:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def op_name(text: str) -> str:
    """`%fusion.8 = f32[...] fusion(...), ...` -> `fusion.8`: the trace
    gives an op's whole HLO instruction as its name."""
    return text.split(" = ", 1)[0].lstrip("%")


def _device_ops(plane) -> list[tuple[str, float, float]]:
    """(name, start, end) of the device's leaf ops. An op that encloses
    others (a `while` or `conditional` and its body's ops) is left out, so
    that each nanosecond counts for one op."""
    for line in plane.lines:
        if line.name == OPS_LINE:
            # an enclosing op sorts before the first op it encloses
            evs = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns,
                           op_name(ev.name)) for ev in line.events),
                         key=lambda t: (t[0], -t[1]))
            leaves = []
            for k, (s, e, n) in enumerate(evs):
                nxt = evs[k + 1] if k + 1 < len(evs) else None
                if nxt is not None and nxt[0] < e and nxt[1] <= e:
                    continue  # encloses the next op
                leaves.append((n, s, e))
            return leaves
    return []


def _label(spans, t: float) -> str:
    inner = [s for s in spans if s[0] != WINDOW and s[1] <= t <= s[2]]
    return max(inner, key=lambda s: s[1])[0] if inner else "none"


def summarize(path, chips: int = 1) -> dict:
    """{busy_s, window_s, device_ops, idle_gaps, op_seconds} of a trace
    file."""
    import jax

    return reduce(jax.profiler.ProfileData.from_file(str(Path(path))), chips)


def reduce(pd, chips: int = 1) -> dict:
    """`summarize` of a `jax.profiler.ProfileData`."""
    planes = list(pd.planes)
    spans = _host_spans(planes)
    devices = sorted((int(DEVICE_PLANE.match(p.name).group(1)), p)
                     for p in planes if DEVICE_PLANE.match(p.name))
    devices = [p for _, p in devices][:chips]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    ops = [_device_ops(p) for p in devices]
    if not any(ops):
        raise ValueError("no device op in the trace")
    win = [s for s in spans if s[0] == WINDOW]
    if win:
        t0, t1 = win[0][1], win[0][2]
    else:
        t0 = min(s for o in ops for _, s, _ in o)
        t1 = max(e for o in ops for _, _, e in o)
    busy, op_s = [], {}
    merged0 = None
    for o in ops:
        inside = [(max(s, t0), min(e, t1), n) for n, s, e in o
                  if e > t0 and s < t1]
        merged = _union([(s, e) for s, e, _ in inside])
        busy.append(sum(e - s for s, e in merged))
        for s, e, n in inside:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
        if merged0 is None:
            merged0 = merged
    gaps = []
    edges = [t0] + [x for iv in merged0 for x in iv] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((_label(spans, (a + b) / 2), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (t1 - t0) * 1e-9,
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": [[n, s] for n, s in gaps[:TOP]],
            "op_seconds": op_s}


def idle_share_pct(ctx) -> float | None:
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_seconds(summary: dict, kernel: str) -> float:
    """Summed device seconds of the ops whose name holds `kernel`."""
    return sum(s for n, s in summary["op_seconds"].items() if kernel in n)
