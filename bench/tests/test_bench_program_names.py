"""The trace reduction on events named the way the program names them:
kernels after their op, host spans from ``repro.core.telemetry``."""
import pytest

import devtrace
from test_bench_devtrace import CHIP_OPS, UTS_OP, _profile


@pytest.mark.parametrize("kernel", sorted(CHIP_OPS))
def test_kernel_matched_under_its_own_name(kernel):
    # the program names each kernel's instruction after it (``call``
    # before); the match reads only the layout
    named = CHIP_OPS[kernel].replace("%call.1 =", f"%{kernel}.1 =")
    kernels = devtrace.kernel_matchers(sorted(CHIP_OPS))
    assert devtrace.op_label(named, kernels) == kernel


def test_gap_named_by_a_program_span_inside_the_task():
    profile = _profile(
        device_ops=[(UTS_OP, 0, 10), (UTS_OP, 40, 50)],
        host_lines={"main": [("job", 0, 50)],
                    "w1": [("pool.task", 5, 48), ("task.uts", 6, 47),
                           ("uts.frontier", 8, 45),
                           ("device.pull", 30, 44)]})
    t = devtrace.reduce_profile(profile)
    assert t.gaps_ns == {"task.uts / uts.frontier": 30}
