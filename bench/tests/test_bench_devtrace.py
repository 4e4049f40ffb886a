"""The trace reduction on a small synthetic trace with known answers."""
import pytest

import devtrace

UTS_OP = ('%call.1 = u32[5,16384]{1,0} custom-call(u32[5,16384]{1,0} %a, '
          'u32[1,16384]{1,0} %b), custom_call_target="tpu_custom_call"')
MS_OP = ('%call.1 = s32[8,256]{1,0} custom-call(f32[8,256]{1,0} %a, '
         'f32[8,256]{1,0} %b), custom_call_target="tpu_custom_call"')
FUSION = "%convert_reduce_fusion.3 = s32[8192]{0} fusion(s32[64]{0} %c)"
#: the kernels' operations as a TPU v5e trace names them
CHIP_OPS = {
    "uts_hash": (
        '%call.1 = u32[5,16384]{1,0:T(8,128)} custom-call(u32[5,16384]'
        '{1,0:T(8,128)} %arrays_0_.1, u32[1,16384]{1,0:T(1,128)} '
        '%bitcast.1), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={u32[5,16384]{1,0}, u32[1,16384]{1,0}}, '
        'frontend_attributes={kernel_metadata={}}'),
    "mandelbrot": (
        '%call.1 = s32[8,64]{1,0:T(8,128)} custom-call(f32[8,64]'
        '{1,0:T(8,128)} %arrays_0_.1, f32[8,64]{1,0:T(8,128)} '
        '%arrays_1_.1), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={f32[8,64]{1,0}, f32[8,64]{1,0}}, '
        'frontend_attributes={kernel_metadata={}}')}
#: other Pallas calls on the same operand types
LOOKALIKES = [
    '%call.1 = f32[8,64]{1,0} custom-call(f32[8,64]{1,0} %a, f32[8,64]{1,0}'
    ' %b), custom_call_target="tpu_custom_call"',
    '%call.1 = s32[8,64]{1,0} custom-call(f32[8,64]{1,0} %a, f32[8,64]{1,0}'
    ' %b, f32[8,64]{1,0} %c), custom_call_target="tpu_custom_call"',
    '%call.1 = s32[8,64]{1,0} custom-call(f32[8,128]{1,0} %a, f32[8,128]'
    '{1,0} %b), custom_call_target="tpu_custom_call"',
    '%call.1 = u32[5,256]{1,0} custom-call(u32[5,256]{1,0} %a), '
    'custom_call_target="tpu_custom_call"',
    '%call.1 = u32[5,256]{1,0} custom-call(u32[5,256]{1,0} %a, u32[1,256]'
    '{1,0} %b), custom_call_target="other_call"',
]


def _plane(pid, name, lines):
    """An XPlane in text form; ``lines`` maps a line name to events
    ``(name, start_ns, end_ns)``."""
    names = sorted({ev[0] for evs in lines.values() for ev in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, evs) in enumerate(lines.items()):
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for n, s, e in evs:
            out.append(f"events {{ metadata_id: {ids[n]} "
                       f"offset_ps: {s * 1000} duration_ps: {(e - s) * 1000} }}")
        out.append("}")
    for n, i in ids.items():
        esc = n.replace("\\", "\\\\").replace('"', '\\"')
        out.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{esc}" }} }}')
    out.append("}")
    return "\n".join(out)


def _profile(device_ops, host_lines):
    from jax.profiler import ProfileData
    text = "\n".join([
        _plane(1, "/device:TPU:0", {"XLA Modules": [], "XLA Ops": device_ops}),
        _plane(2, "/host:CPU", host_lines)])
    return ProfileData.from_text_proto(text)


def test_busy_window_kernels_and_gaps():
    profile = _profile(
        device_ops=[(UTS_OP, 10, 30), (FUSION, 20, 40), (MS_OP, 60, 70),
                    (UTS_OP, 150, 160)],              # after the last job
        host_lines={
            "main": [("job", 0, 100)],
            "worker": [("task.uts", 5, 50), ("PjitFunction(call)", 8, 45),
                       ("task.uts", 55, 95), ("DevicePut", 71, 90)],
        })
    t = devtrace.reduce_profile(profile, ["uts_hash", "mandelbrot"])
    assert t.window_ns == 100
    assert t.busy_ns == 30 + 10                     # [10,40] and [60,70]
    assert t.kernel_ns("uts_hash") == 20            # the late one is outside
    assert t.kernel_ns("mandelbrot") == 10
    assert t.ops_ns["convert_reduce_fusion"] == 20
    # [0,10]: job only; [40,60]: job (task 5-50 does not cover it);
    # [70,100]: task 55-95 does not cover all of it either
    assert t.gaps_ns == {"job": 10 + 20 + 30}
    b = t.breakdown()
    assert b["device_ops"][0] == ["uts_hash", 20e-9]
    assert b["idle_gaps"] == [["job", 60e-9]]
    with pytest.raises(KeyError):
        t.kernel_ns("flash_attention")


def test_gap_named_by_the_host_span_that_covers_it():
    profile = _profile(
        device_ops=[(FUSION, 0, 10), (FUSION, 40, 50)],
        host_lines={"main": [("job", 0, 50)],
                    "w1": [("task.mariani_silver", 5, 45),
                           ("PjitFunction(concatenate)", 8, 42),
                           ("ParseArguments", 20, 21)],
                    "w2": [("np.asarray(jax.Array)", 2, 48)]})
    t = devtrace.reduce_profile(profile)
    assert t.gaps_ns == {
        "task.mariani_silver / PjitFunction(concatenate)": 30}
    assert t.busy_ns == 20 and t.window_ns == 50


def test_no_device_ops_reads_idle():
    profile = _profile([], {"main": [("job", 0, 10)]})
    t = devtrace.reduce_profile(profile)
    assert t.busy_ns == 0 and t.window_ns == 10


@pytest.mark.parametrize("kernel", sorted(CHIP_OPS))
def test_kernel_matched_by_its_operand_layout(kernel):
    kernels = devtrace.kernel_matchers(sorted(CHIP_OPS))
    assert devtrace.op_label(CHIP_OPS[kernel], kernels) == kernel
    for other in LOOKALIKES:
        assert devtrace.op_label(other, kernels) == "call", other


def test_union():
    total, merged = devtrace.union_ns([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert total == 3 + 4 and merged == [(0, 3), (5, 9)]
