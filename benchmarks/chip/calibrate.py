"""Read the numbers that `correct` compares, for setting their limits: the
program's sound runs over many seeds, the control's, and the train faults',
all in one process so that set-up and compiles are paid once.

    python benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... [--control-seeds 21,22,23] \
        [--fault-seeds 31,32,33] [--seconds 2] [--numbers a,b] \
        [--out FILE]

Each program seed is a whole run of the cell (set-up, a short window, the
check) through `harness.run`, with the plan and the mesh kept between
seeds. The control is the kind's `control` (the reference one precision
below the configuration's, in the program's place). `--numbers` reads
numbers the kind's check offers besides those the cell's limits name. The
train faults are
planted under the timed path: `half_batch` takes the loss over half of
each batch. Prints one JSON line per reading and writes them all to
`--out`. Needs a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


@contextlib.contextmanager
def half_batch(kind):
    """The loss over the first half of each microbatch, the mean taken
    over the rest."""
    loss = kind.loss

    def half(cfg, integ, params, patches, labels):
        h = patches.shape[0] // 2
        return loss(cfg, integ, params, patches[:h], labels[:h])

    kind.loss = half
    try:
        yield
    finally:
        kind.loss = loss


# the faults planted under each kind's timed path and read on the chip (a
# state left unchanged reads 1 by the measure and needs no run)
FAULTS = {"vit_train": {"half_batch": half_batch}}


def seeds(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--numbers", type=lambda s: [x for x in s.split(",")
                                                   if x], default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.entry(bench["workloads"], args.workload, "workload")
    config = harness.config_of(bench, cell["config"])
    traffic = harness.traffic_of(cell["traffic"])
    harness.configure_cache()
    try:
        harness.device_info(cell["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    kind = harness.kind_of(config["kind"])
    # the extra numbers get no limit: only their readings are wanted
    limits = dict({k: float("inf") for k in args.numbers},
                  **harness.limits_of(args.workload))
    memo, out = {}, []

    def emit(rec):
        out.append(rec)
        print(json.dumps(rec), flush=True)

    for s in args.seeds:
        t0 = time.perf_counter()
        r = harness.run(args.workload, s, args.seconds, False,
                        t_start=t0, bench=bench, limits=limits, memo=memo)
        emit({"reading": "program", "seed": s, "correct": r["correct"],
              "attempted": r["attempted"], "failed": r["failed"],
              "numbers": {k: c["value"] for k, c in r["checks"].items()},
              "metrics": {k: m["value"] for k, m in r["metrics"].items()}})
    for s in args.control_seeds:
        with harness.no_fallback():
            nums = kind.control(config, traffic, s, memo)
        emit({"reading": "control", "seed": s, "numbers": nums})
    for name, plant in FAULTS.get(config["kind"], {}).items():
        for s in args.fault_seeds:
            t0 = time.perf_counter()
            with plant(kind):
                r = harness.run(args.workload, s, args.seconds, False,
                                t_start=t0, bench=bench, limits=limits,
                                memo=memo)
            emit({"reading": f"fault:{name}", "seed": s,
                  "correct": r["correct"],
                  "numbers": {k: c["value"] for k, c in r["checks"].items()}})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
