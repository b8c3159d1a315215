"""The `topolm_prefill` kind at a size a CPU test run can hold: its
reference against the plain one in `src/` (the explicit masked quadratic
form) and its route gap, a sound run, the three controls and the faults
planted under the timed path, and its work counts at the published
widths."""
import contextlib

import numpy as np
import pytest

import harness
import lm_small
import topolm_prefill_ref as ref
from lm_work import lightning_sweep_work, prefill_flops

KIND = harness.kind_of("topolm_prefill")


def test_reference_matches_the_plain_reference():
    """The benchmark's reference, in blocks of rows with the lightning state
    carried between them, equals `repro.testing.minimax_ref` on the same
    weights, prompt and positions."""
    import jax.numpy as jnp
    from repro.testing import minimax_ref

    c = lm_small.config(dtype="float32")
    params = ref.init_params(7, c, jnp.float32)
    toks = ref.prompts(7, c, (2, 40), 1)[0]
    got = ref.Reference(c, rows=16).last_logits(params, toks, 3)
    cfg = KIND.model_config(c)
    for b in range(2):
        want = np.asarray(minimax_ref.forward(cfg, params, toks[b]))
        assert ref.gap(got[b], want[-3:, :c["vocab_size"]]) < 1e-5


def test_route_gap_reads_a_wrong_choice():
    """Given its own top-k choices, the reference reads the same logits and
    a route gap of 0; given the least likely expert in place of one token's
    second choice, a gap of its logit's distance to the second."""
    import jax.numpy as jnp

    c = lm_small.config(dtype="float32")
    params = ref.init_params(7, c, jnp.float32)
    toks = ref.prompts(7, c, (1, 40), 1)[0]
    r = ref.Reference(c, rows=16, max_len=64)
    own, routes, gap0 = r.forward(params, toks, 3)
    again, _, gap1 = r.forward(params, toks, 3, routes)
    assert gap0 == 0.0 and gap1 == 0.0
    np.testing.assert_array_equal(own, again)
    wrong = [[t.copy() for t in row] for row in routes]
    used = set(wrong[0][3][20])
    wrong[0][3][20, 1] = next(e for e in range(32) if e not in used)
    _, _, gap2 = r.forward(params, toks, 3, wrong)
    assert gap2 > 0.0


@pytest.mark.parametrize("seed", [7, 9])
def test_balanced_routers_spread_the_load(seed):
    """`balance_routers` changes the routers alone, and with them no expert
    takes the load that a random router sends to its favourites: on a
    seeded prompt the most that one expert takes in a layer stays under 2.5
    times the mean, where the init's routers pass it."""
    import jax
    import jax.numpy as jnp

    c = lm_small.config()
    cfg = KIND.model_config(c)
    raw = ref.init_params(seed, c, jnp.bfloat16)
    bal = ref.balance_routers(raw, c, seed, 256)
    same = jax.tree.map(lambda a, b: bool((a == b).all()), raw, bal)
    for key, blk in same["blocks0"].items():
        assert not blk["moe"].pop("router"), key
    assert all(jax.tree.leaves(same))
    toks = jnp.asarray(ref.prompts(seed, c, (1, 512), 1)[0])
    most = []
    for params in (raw, bal):
        _, cache = jax.jit(KIND.prefill(cfg, 512))(params, toks)
        most.append(max(np.bincount(log[:512].ravel(), minlength=32).max()
                        for log in KIND.route_log(c, cache)[0]))
    mean = 512 * 2 / 32
    assert most[1] < 2.5 * mean < most[0]


def test_sound_run_is_correct():
    out = lm_small.run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("variant", ["fp8", "no_decay", "normalized"])
def test_control_is_not_correct(variant):
    nums = KIND.control(lm_small.config(), dict(lm_small.TRAFFIC),
                        2**31 + 5, None, variant)
    assert any(nums[k] > v for k, v in lm_small.LIMITS.items()), nums


@contextlib.contextmanager
def half_prompt():
    """Each call prefills the first half of its prompts."""
    prefill = KIND.prefill

    def make(cfg, S):
        fn = prefill(cfg, S)
        return lambda params, toks: fn(params, toks[:, :toks.shape[1] // 2])

    KIND.prefill = make
    try:
        yield
    finally:
        KIND.prefill = prefill


@contextlib.contextmanager
def second_expert_dropped():
    """The program computes each token's first expert alone, at its
    renormalized weight, and still logs both choices."""
    from repro.models import moe

    gates = moe._top_k_gates

    def first_only(probs, k):
        g, ids = gates(probs, k)
        return g.at[:, 1:].set(0.0), ids

    moe._top_k_gates = first_only
    try:
        yield
    finally:
        moe._top_k_gates = gates


def test_half_prompt_is_caught():
    with half_prompt():
        out = lm_small.run()
    assert not out["correct"], out["checks"]


def test_second_expert_dropped_is_caught():
    with second_expert_dropped():
        out = lm_small.run()
    assert not out["correct"], out["checks"]


def test_work_at_published_widths():
    """A call is 346-407 TFLOP over the host's share; the lightning
    layers' projections take 56.7-66.8% of it, the decay sweep about 1%."""
    bench = harness.load_benchmark()
    c = harness.config_of(bench, "minimax-text-01")
    shapes = [(8, 8192), (4, 16384), (2, 32768), (1, 65536)]
    total = [prefill_flops(c, s) for s in shapes]
    assert 3.4e14 < min(total) and max(total) < 4.1e14
    z = ref.sizes(c)
    T = 65536
    light = 7 * 2.0 * T * (z["d"] * 4 * z["H"] * z["hd"]
                           + z["H"] * z["hd"] * z["d"])
    assert 0.566 < light / max(total) and light / min(total) < 0.669
    sweep, nbytes = lightning_sweep_work(c, shapes[0])
    assert 0.005 < sweep / min(total) < 0.015
    # bf16 q, k, v in; f32 sum and row sums out
    assert nbytes == 7 * 64 * T * (3 * 128 * 2 + 4 * (128 + 1))
