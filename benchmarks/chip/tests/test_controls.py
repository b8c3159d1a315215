"""The control (the reference one precision below the configuration's, in
the program's place) comes out not correct by the cell's own limits, at a
size a CPU test run can hold; and the program at that size stays inside
them."""
import pytest

import harness
import small

# the icosphere at 6 subdivisions (40,962 vertices, full width d = 3):
# the largest mesh a CPU run builds and integrates in seconds
MESH = {"subdivisions": 6, "n": 40962}


def _fails(nums: dict, limits: dict) -> bool:
    return any(nums[k] > v for k, v in limits.items() if k in nums)


@pytest.mark.parametrize("workload", ["mesh7-rational-pallas"])
def test_mesh_control_is_not_correct(workload):
    cell = harness.entry(small.BENCH["workloads"], workload, "workload")
    traffic = harness.traffic_of(cell["traffic"])
    limits = harness.limits_of(workload)
    kind = harness.kind_of("mesh_integrate")
    nums = kind.control(small.config(workload, **MESH), traffic, 2**31 + 5)
    assert _fails(nums, limits), nums


def test_train_control_is_not_correct():
    workload = "topovit-b16-train"
    cell = harness.entry(small.BENCH["workloads"], workload, "workload")
    traffic = harness.traffic_of(cell["traffic"])
    kind = harness.kind_of("vit_train")
    nums = kind.control(small.config(workload), traffic, 2**31 + 5)
    assert _fails(nums, small.limits(workload)), nums
