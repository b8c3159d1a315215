"""The trace reduction's arithmetic on a hand-made trace in the TPU
profiler's layout: a `/device:TPU:0` plane with an `XLA Ops` line, and
the harness's annotations on a `/host:CPU` thread. Times in microseconds
from the line's start. Then the same reduction on a trace recorded on a
TPU v5e by `record_fixture.py` (`data/fixture.xplane.pb`)."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import trace_reader

# device: while.1 [0, 50) encloses fusion.2 [0, 20) and fusion.3
# [30, 50); then custom-call fdist_matvec_batched_pallas.4 [60, 90) and
# fusion.2 again [150, 170). Host: bench.window [0, 200), bench.wait
# [0, 95), bench.prepare [95, 140), bench.dispatch [140, 150).
OPS = [(1, 0, 50), (2, 0, 20), (3, 30, 20), (4, 60, 30), (2, 150, 20)]
NAMES = {1: "%while.1 = (s32[]) while(s32[] %p)",
         2: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a)",
         3: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %b)",
         4: "%fdist_matvec_batched_pallas.4 = f32[2,8,64]{2,1,0} "
            "custom-call(...)"}
HOST = [(11, 0, 200), (12, 0, 95), (13, 95, 45), (14, 140, 10)]
HOST_NAMES = {11: "bench.window", 12: "bench.wait", 13: "bench.prepare",
              14: "bench.dispatch"}


def _events(items):
    return "".join(f"events {{ metadata_id: {m} offset_ps: {s * 10**6} "
                   f"duration_ps: {d * 10**6} }}\n" for m, s, d in items)


def _meta(names):
    return "".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                   f'name: "{v}" }} }}\n' for k, v in names.items())


@pytest.fixture(scope="module")
def summary():
    text = (
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
        + _events(OPS) + "} " + _meta(NAMES) + "} "
        'planes { id: 2 name: "/host:CPU" '
        'lines { id: 2 name: "python" timestamp_ns: 0 '
        + _events(HOST) + "} " + _meta(HOST_NAMES) + "}")
    return trace_reader.reduce(ProfileData.from_text_proto(text))


def test_window_and_busy_union(summary):
    assert summary["window_s"] == pytest.approx(200e-6)
    # busy: [0, 20) + [30, 50) + [60, 90) + [150, 170) = 90 us; the
    # enclosing while op is not counted over its body's gap
    assert summary["busy_s"] == pytest.approx(90e-6)
    assert trace_reader.idle_share_pct({"trace": summary}) == \
        pytest.approx(55.0)


def test_ops_are_leaves_with_short_names(summary):
    ops = dict(summary["device_ops"])
    assert "while.1" not in ops
    assert ops == pytest.approx({"fusion.2": 40e-6, "fusion.3": 20e-6,
                                 "fdist_matvec_batched_pallas.4": 30e-6})
    assert [n for n, _ in summary["device_ops"]][0] == "fusion.2"
    assert trace_reader.kernel_seconds(
        summary, "fdist_matvec_batched_pallas") == pytest.approx(30e-6)


def test_idle_gaps_are_named_by_the_host(summary):
    # gaps: [20, 30) wait, [50, 60) wait, [90, 150) prepare (middle 120),
    # [170, 200) window only -> "none"
    gaps = summary["idle_gaps"]
    assert gaps[0] == ["bench.prepare", pytest.approx(60e-6)]
    assert gaps[1] == ["none", pytest.approx(30e-6)]
    assert sorted(n for n, _ in gaps[2:]) == ["bench.wait", "bench.wait"]


@pytest.fixture(scope="module")
def recorded():
    return trace_reader.summarize(Path(__file__).parent / "data"
                                  / "fixture.xplane.pb")


def test_recorded_trace(recorded):
    """Four kernel calls and three small matmuls inside `bench.window`,
    with a 20-ms host pause under `bench.prepare`: the device plane, the
    kernel's custom call and the pause are all found."""
    assert 0.02 < recorded["window_s"] < 0.1
    assert 0 < recorded["busy_s"] < 0.01 * recorded["window_s"]
    assert trace_reader.kernel_seconds(
        recorded, "fdist_matvec_batched_pallas") > 0
    name, gap = recorded["idle_gaps"][0]
    assert name == "bench.prepare" and 0.02 <= gap < 0.03
