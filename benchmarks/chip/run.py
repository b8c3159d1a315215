"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (inputs and weights from the seed, plan build, compile, warm-up),
then a closed loop measured for `--seconds`, then the check of what that
loop produced against the plain reference. The last line of standard
output is one JSON object: `correct`, `attempted`, `failed`, `metrics`
(the cell's end-to-end metrics with `--trace 0`, its per-layer metrics
with `--trace 1`), `device`, with `--trace 1` a `breakdown`, and last
`checks`, each compared number beside its limit. Exits 2, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for;
exits 3, printing no result, where a metric the cell reports reads nothing.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except harness.MissingMetric as e:
        print(f"bench: {e.args[0]}", file=sys.stderr)
        print_checks(e.args[1])
        return 3
    print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
