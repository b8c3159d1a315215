"""The benchmark's shared machinery: lookup by name, the chip check, the
compile cache, the measured window, the traced window and the result line.

Everything particular to one configuration, traffic mix, cell or metric
lives in a file of its own that is found by the name `BENCHMARK.json`
gives it:

    configs/<config>.json     sizes as run; names its `kind`
    kinds/<kind>.py           builds the inputs, the entry and the check
    kinds/<kind>_ref.py       the plain reference (imports nothing of repro)
    traffic/<traffic>.json    the traffic mix's parameters
    limits/<workload>.json    the limit of each number `correct` compares
    metrics/<metric>.py       read(ctx) -> number, or None where absent

A kind module provides `setup(config, traffic, seed, host, memo)`, which
returns a cell object with `step()`, `after_window()`, `free()` and
`check()`, and `work` counts for the metric readers.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import statistics
import sys
import time
import warnings
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# fixed paths inside the checkout: the compile cache's path is part of its
# key, so only a directory that never moves is found again by the next run
CACHE_DIR = HERE / ".jax_cache"
TRACE_DIR = HERE / ".trace"
for _p in (ROOT / "src", HERE, HERE / "kinds"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class MissingMetric(RuntimeError):
    """A metric that BENCHMARK.json gives the cell read nothing in it; the
    second argument holds the run's checks."""


# ----------------------------------------------------------------------------
# lookup by name
# ----------------------------------------------------------------------------


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path):
    """Import a file by path; names may hold '.' and '-'."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def config_of(bench: dict, name: str, base: Path = ROOT) -> dict:
    e = entry(bench["configs"], name, "configuration")
    return dict(load_json(base / e["file"]), name=name)


def traffic_of(name: str, base: Path = HERE) -> dict:
    return dict(load_json(base / "traffic" / f"{name}.json"), name=name)


def limits_of(workload: str, base: Path = HERE) -> dict:
    return load_json(base / "limits" / f"{workload}.json")


def kind_of(kind: str, base: Path = HERE):
    return load_module(base / "kinds" / f"{kind}.py")


def reader_of(metric: str, base: Path = HERE):
    return load_module(base / "metrics" / f"{metric}.py")


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (trace off) or per-layer metrics
    (trace on). A metric without `workloads` belongs to every cell that
    reports what it moves (per-layer) or to every cell (end-to-end)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# ----------------------------------------------------------------------------
# the chip, the compile cache, host spans
# ----------------------------------------------------------------------------


def configure_cache() -> str:
    """Keep JAX's persistent compilation cache inside the checkout. A
    `JAX_COMPILATION_CACHE_DIR` that points elsewhere does not outlast the
    checkout's runs, so it is not used."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = str(CACHE_DIR)
    if env and Path(env).resolve().is_relative_to(ROOT):
        path = env
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program this cell compiles, the small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int | None:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Host:
    """The harness's host spans: a `TraceAnnotation` for the profiler's
    trace and a host-clock timing for the metrics, under one name."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str, timed: bool = False):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        if timed:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t0)


@contextlib.contextmanager
def no_fallback():
    """A ladder demotion is an error, and the disk plan cache is off, so
    each run takes the path its traffic names and builds its plan."""
    from repro.core import ladder, plan_cache

    plan_cache.configure(None)
    with warnings.catch_warnings():
        warnings.simplefilter("error", ladder.BackendDemotionWarning)
        yield


def ladder_clean() -> bool:
    from repro.core import ladder

    st = ladder.stats()
    return (st["demotions"] == 0 and st["errors"] == 0
            and st["nonfinite"] == 0 and not st["blocked"])


# ----------------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------------


def window(cell, seconds: float, host: Host) -> dict:
    """Closed loop with one caller: call `cell.step()` until `seconds`
    have passed; the window ends when the last call completes. Each call
    is timed from its dispatch to its result."""
    lat, failed = [], 0
    t0 = time.perf_counter()
    while True:
        ts = time.perf_counter()
        try:
            ok = cell.step(host)
        except Exception as e:  # noqa: BLE001 - a failed call is counted
            print(f"bench: call {len(lat)} raised {type(e).__name__}: {e}",
                  file=sys.stderr)
            ok = False
        te = time.perf_counter()
        lat.append(te - ts)
        failed += not ok
        if te - t0 >= seconds:
            break
    return {"seconds": te - t0, "calls": len(lat), "latencies_s": lat,
            "failed_calls": failed}


def traced_window(cell, seconds: float, host: Host) -> tuple[dict, Path]:
    import jax

    TRACE_DIR.mkdir(parents=True, exist_ok=True)
    for old in TRACE_DIR.rglob("*.xplane.pb"):
        old.unlink()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            w = window(cell, seconds, host)
    finally:
        jax.profiler.stop_trace()
    return w, next(TRACE_DIR.rglob("*.xplane.pb"))


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, bench: dict | None = None, config: dict | None = None,
        traffic: dict | None = None, limits: dict | None = None,
        require_chip: bool = True, memo: dict | None = None,
        strict: bool | None = None) -> dict:
    """One run of one cell. Returns the result object; raises `NoChip`
    before any work where the chip is missing, and `MissingMetric` where a
    metric the cell reports reads nothing (`strict`, on by default where
    the chip is required; otherwise such a metric is left out)."""
    bench = load_benchmark() if bench is None else bench
    cell_e = entry(bench["workloads"], workload, "workload")
    config = config_of(bench, cell_e["config"]) if config is None else config
    traffic = traffic_of(cell_e["traffic"]) if traffic is None else traffic
    limits = limits_of(workload) if limits is None else limits
    chips = cell_e["chips"]
    if require_chip:
        configure_cache()
        device = device_info(chips)
    else:
        import jax

        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    kind = kind_of(config["kind"])
    host = Host()
    with no_fallback():
        cell = kind.setup(config, traffic, seed, host, memo)
        setup_s = time.perf_counter() - t_start
        if trace:
            w, xplane = traced_window(cell, seconds, host)
        else:
            w, xplane = window(cell, seconds, host), None
        peak = memory_peak_bytes(chips)
        late_failed = cell.after_window()
        clean = ladder_clean()
    cell.free()
    checks = cell.check(limits)
    ctx = {"window": w, "setup_s": setup_s, "host_s": dict(host.seconds),
           "work": cell.work, "memory_peak_bytes": peak, "chips": chips,
           "config": config, "traffic": traffic, "device": device}
    if require_chip:
        from peaks import peaks

        ctx["peak"] = peaks(device["kind"])
    device = dict(device, memory_peak_bytes=peak)
    out = {"correct": False, "attempted": w["calls"],
           "failed": w["failed_calls"] + late_failed + (0 if clean else 1),
           "metrics": {}, "device": device}
    if xplane is not None:
        from trace_reader import summarize

        ts = summarize(xplane, chips=chips)
        ctx["trace"] = ts
        device.update(busy_s=ts["busy_s"], window_s=ts["window_s"])
        out["breakdown"] = {"device_ops": ts["device_ops"],
                            "idle_gaps": ts["idle_gaps"]}
    missing = []
    for m in metrics_for(bench, workload, trace):
        v = reader_of(m["name"]).read(ctx)
        if v is None:
            missing.append(m["name"])
        else:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    if missing and (require_chip if strict is None else strict):
        raise MissingMetric(f"{workload} read nothing for {missing}", checks)
    passed = all(c["value"] <= c["limit"] for c in checks.values())
    out["correct"] = bool(passed and out["failed"] == 0)
    out["checks"] = checks
    return out


def quantile(values, q: int, n: int = 100) -> float:
    """The q-th of n quantiles, as `statistics.quantiles` gives them."""
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=n)[q - 1])
