"""The scope reader (`scopes.py`) and the per-layer metrics that read it.

First on a hand-made trace in the TPU profiler's layout, whose event
metadata holds `tf_op` and `program_id` stats as the chip writes them; then
on a trace recorded on a TPU v5e by `record_scope_fixture.py`
(`data/scopes.xplane.pb`): a scoped `ftfi.apply` on the pallas backend and
its finite check under the harness's annotations."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import harness
import scopes
import trace_reader

DATA = Path(__file__).parent / "data"
EXEC_METRICS = ("exec_leaf_ms", "exec_gather_ms", "exec_cross_ms",
                "exec_scatter_ms", "exec_rest_ms")
STEP_METRICS = ("step_alg1_ms", "step_attn_ms", "step_mlp_ms",
                "step_adamw_ms", "step_rest_ms")

# Program 11 runs [0, 100) us, program 22 [120, 160); the window is
# [5, 130). (metadata id, start, duration, op name, tf_op, program)
OPS = [
    (1, 0, 10, "fusion.1",
     "jit(step)/while/body/closed_call/jvp(vit.attn)/vit.alg1/"
     "ftfi.cross/mul:", 11),
    (2, 10, 20, "fusion.2", "jit(step)/transpose(jvp(vit.mlp))/dot_general:",
     11),
    (3, 30, 15, "dynamic-update-slice.3",
     "jit(step)/while/body/closed_call/transpose(jvp(vit.layer_params))/"
     "pad:", 11),
    (4, 45, 5, "fusion.4", "jit(step)/vit.attn/mul;jvp(vit.mlp)/add:", 11),
    (5, 50, 2, "copy-start", None, 11),
    (6, 60, 10, "fusion", "jit(step)/adamw/add:", 11),
    (7, 120, 20, "fusion", "jit(finite)/reduce_and:", 22),
]


def _hand_made() -> bytes:
    us = 10**6  # ps
    evs = "".join(f"events {{ metadata_id: {m} offset_ps: {s * us} "
                  f"duration_ps: {d * us} }} " for m, s, d, *_ in OPS)
    meta = ""
    for m, _, _, name, tf, prog in OPS:
        stats = f"stats {{ metadata_id: 2 uint64_value: {prog} }} "
        if tf is not None:
            stats += f'stats {{ metadata_id: 1 str_value: "{tf}" }} '
        meta += (f'event_metadata {{ key: {m} value {{ id: {m} '
                 f'name: "%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %a)" '
                 f'{stats}}} }} ')
    text = (
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0 '
        f'events {{ metadata_id: 100 offset_ps: 0 duration_ps: {100 * us} }} '
        f'events {{ metadata_id: 101 offset_ps: {120 * us} '
        f'duration_ps: {40 * us} }} }} '
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0 ' + evs + '} '
        + meta +
        'event_metadata { key: 100 value { id: 100 '
        'name: "jit_step(11)" } } '
        'event_metadata { key: 101 value { id: 101 '
        'name: "jit_finite(22)" } } '
        'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } '
        'stat_metadata { key: 2 value { id: 2 name: "program_id" } } } '
        'planes { id: 2 name: "/host:CPU" '
        'lines { id: 3 name: "python" timestamp_ns: 0 '
        f'events {{ metadata_id: 1 offset_ps: {5 * us} '
        f'duration_ps: {125 * us} }} }} '
        'event_metadata { key: 1 value { id: 1 name: "bench.window" } } }')
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture(scope="module")
def hand_made(tmp_path_factory) -> Path:
    path = tmp_path_factory.mktemp("trace") / "hand.xplane.pb"
    path.write_bytes(_hand_made())
    return path


def _ctx(monkeypatch, path: Path, calls: int) -> dict:
    monkeypatch.setattr(scopes, "trace_file", lambda: path)
    return {"trace": trace_reader.summarize(path), "chips": 1,
            "window": {"calls": calls}}


def test_metadata_join_by_program():
    meta = scopes.op_metadata(_hand_made())["/device:TPU:0"]
    # the same op name in two programs keeps both name stacks
    assert meta[(11, "fusion")] == "jit(step)/adamw/add:"
    assert meta[(22, "fusion")] == "jit(finite)/reduce_and:"
    assert (11, "copy-start") not in meta  # no tf_op stat
    ops = scopes.scoped_ops(_hand_made())
    stacks = [tf for n, tf, _ in ops if n == "fusion"]
    assert stacks == ["jit(step)/adamw/add:", "jit(finite)/reduce_and:"]
    # clipped to the window: fusion.1 [0, 10) counts from 5, the finite
    # check's fusion [120, 140) up to 130
    secs = {(n, tf): s for n, tf, s in ops}
    assert secs[("fusion.1", OPS[0][4])] == pytest.approx(5e-6)
    assert secs[("fusion", OPS[6][4])] == pytest.approx(10e-6)


@pytest.mark.parametrize("stack,want", [
    ("jit(f)/vit.attn/vit.alg1/ftfi.cross/mul:", "vit.alg1"),
    ("jit(f)/while/body/jvp(vit.attn)/dot_general:", "vit.attn"),
    ("jit(f)/transpose(jvp(vit.mlp))/dot_general:", "vit.mlp"),
    ("jit(f)/transpose(jvp(jvp()))/checkpoint/vit.mlp/mul", "vit.mlp"),
    ("jit(f)/vit.attn/mul;jvp(vit.mlp)/add", "vit.attn"),
    ("jit(f)/vit.attnx/vit.mlp.y/add", None),
    ("jit(f)/adamw/jit(clip)/max:", "adamw"),
    ("", None),
])
def test_innermost_scope(stack, want):
    assert scopes.innermost(stack, scopes.STEP) == want


def test_step_metrics_add_up_to_busy(monkeypatch, hand_made):
    ctx = _ctx(monkeypatch, hand_made, calls=2)
    got = {m: harness.reader_of(m).read(ctx) for m in STEP_METRICS}
    # us per call: alg1 5, mlp 20, attn 5, adamw 10; the rest holds the
    # layer slice's 15, the unnamed copy's 2 and the finite check's 10
    want = {"step_alg1_ms": 2.5e-3, "step_attn_ms": 2.5e-3,
            "step_mlp_ms": 10e-3, "step_adamw_ms": 5e-3,
            "step_rest_ms": 13.5e-3}
    assert got == pytest.approx(want)
    busy_ms = 1e3 * ctx["trace"]["busy_s"] / 2
    assert sum(got.values()) == pytest.approx(busy_ms)


def test_exec_metrics_add_up_to_busy(monkeypatch, hand_made):
    ctx = _ctx(monkeypatch, hand_made, calls=1)
    got = {m: harness.reader_of(m).read(ctx) for m in EXEC_METRICS}
    assert got["exec_cross_ms"] == pytest.approx(5e-3)
    assert got["exec_leaf_ms"] == got["exec_gather_ms"] == 0.0
    assert sum(got.values()) == pytest.approx(1e3 * ctx["trace"]["busy_s"])


def test_a_program_without_scopes_reads_all_as_rest(monkeypatch):
    """The trace of a program that names no phase (`fixture.xplane.pb`):
    every scope metric reads 0 and each rest metric the whole busy time, so
    that the harness, which fails a run on a metric that reads nothing,
    still gives a result there; the span metrics read 0 where the program
    keeps no span seconds. An untraced run reads nothing."""
    ctx = _ctx(monkeypatch, DATA / "fixture.xplane.pb", calls=4)
    busy_ms = 1e3 * ctx["trace"]["busy_s"] / 4
    for ms, rest in ((EXEC_METRICS, "exec_rest_ms"),
                     (STEP_METRICS, "step_rest_ms")):
        got = {m: harness.reader_of(m).read(ctx) for m in ms}
        assert got.pop(rest) == pytest.approx(busy_ms)
        assert set(got.values()) == {0.0}
    from repro.analysis import trace_guard

    monkeypatch.delattr(trace_guard, "seconds")
    for m in ("plan_decompose_s", "plan_assemble_s"):
        assert harness.reader_of(m).read(ctx) == 0.0
    ctx["trace"] = None  # an untraced run
    assert harness.reader_of("exec_cross_ms").read(ctx) is None


def test_recorded_scopes_add_up_to_busy():
    """On the chip's trace every executor phase holds device time, the
    kernel's custom calls lie inside `ftfi.cross`, and the four phases plus
    the rest equal the busy time `trace_reader` reads (within 1 %)."""
    path = DATA / "scopes.xplane.pb"
    ops = scopes.scoped_ops(path.read_bytes())
    parts = scopes.attribute(ops, scopes.EXEC)
    for sc in scopes.EXEC:
        assert parts[sc] > 0, sc
    summary = trace_reader.summarize(path)
    assert sum(parts.values()) == pytest.approx(summary["busy_s"], rel=0.01)
    kernel = trace_reader.kernel_seconds(summary,
                                         "fdist_matvec_batched_pallas")
    assert 0 < kernel <= parts["ftfi.cross"]
    in_cross = sum(s for n, tf, s in ops
                   if n.startswith("fdist_matvec_batched_pallas")
                   and scopes.innermost(tf, scopes.EXEC) == "ftfi.cross")
    assert in_cross == pytest.approx(kernel)
