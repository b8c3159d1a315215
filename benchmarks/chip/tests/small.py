"""Small sizes of the benchmark's configurations that a CPU test run can
hold: the same code paths, cut in size only."""
import time

import harness

BENCH = harness.load_benchmark()

SMALL = {
    "mesh_integrate": {"subdivisions": 3, "n": 642},
    "vit_train": {"d_model": 64, "num_heads": 4, "num_kv_heads": 4,
                  "head_dim": 16, "d_ff": 128, "num_prefix_embeddings": 16,
                  "patch_dim": 48, "num_classes": 10},
}


# limits of the train cell at its small size, from sound CPU runs there
# (loss_gap <= 6.3e-4, grad_gap <= 2.0e-3, change_gap <= 0.043 over
# seeds): bf16 rounds a small model's updates more coarsely than the
# full-width one's, so the cell's own limits would fail sound small runs
LIMITS = {"vit_train": {"loss_gap": 3e-3, "grad_gap": 1e-2,
                        "change_gap": 0.1}}


def limits(workload: str) -> dict:
    kind = config(workload)["kind"]
    return LIMITS.get(kind) or harness.limits_of(workload)


def config(workload: str, **over) -> dict:
    cell = harness.entry(BENCH["workloads"], workload, "workload")
    c = harness.config_of(BENCH, cell["config"])
    c.update(SMALL[c["kind"]], **over)
    return c


def run(workload: str, seed: int = 2**31 + 11, seconds: float = 0.3,
        strict: bool = False, **over) -> dict:
    """One run of the cell at its small size, past the chip check."""
    return harness.run(workload, seed, seconds, False,
                       t_start=time.perf_counter(), bench=BENCH,
                       config=config(workload, **over),
                       limits=limits(workload), require_chip=False,
                       strict=strict)
