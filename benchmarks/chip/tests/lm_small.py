"""A size of the `topolm_prefill` cell that a CPU test run can hold: the
configuration at a few dozen widths and short prompts, the same code
paths."""
import time

import harness

BENCH = harness.load_benchmark()
WORKLOAD = "topolm-prefill"

SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "rotary_dim": 8,
         "intermediate_size": 32, "vocab_size": 512}
TRAFFIC = {"shapes": [[2, 16], [1, 32]], "pool": 2,
           "check_calls": 2, "decode_steps": 2, "ref_rows": 16}
# limits at this size, from sound CPU runs here (logit_gap 0.013-0.016,
# decode_gap 0.010-0.014 over 3 seeds: bf16 weights and activations against
# the float32 reference) and the controls (fp8 0.092, no decay 0.28,
# normalized 0.45 or more)
LIMITS = {"logit_gap": 0.04, "decode_gap": 0.04, "route_gap": 1.0,
          "nonfinite_calls": 0}


def config(**over) -> dict:
    cell = harness.entry(BENCH["workloads"], WORKLOAD, "workload")
    c = harness.config_of(BENCH, cell["config"])
    c.update(SMALL, **over)
    return c


def run(seed: int = 2**31 + 11, seconds: float = 0.3, **over) -> dict:
    """One run of the cell at its small size, past the chip check."""
    return harness.run(WORKLOAD, seed, seconds, False,
                       t_start=time.perf_counter(), bench=BENCH,
                       config=config(**over), traffic=dict(TRAFFIC),
                       limits=LIMITS, require_chip=False, strict=False)
