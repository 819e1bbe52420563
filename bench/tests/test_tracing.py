"""The trace reducer, on a small trace recorded on a TPU v5e (150 ms of the
approx offline cell's device plane, cut by ``make_trace_fixture.py``, in
which an admit prefills 2048 rows through ``approx_matmul``) and on a
hand-made one whose answers are known exactly."""
import pathlib

import jax
import pytest

import harness
import tracing

FIXTURE = pathlib.Path(__file__).parent / "trace_fixture.pbtxt"


def load_text(text):
    return tracing.from_profile(jax.profiler.ProfileData.from_text_proto(text))


def plane(name, lines):
    metas, body = {}, []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = []
        for ename, start_ns, dur_ns in events:
            mid = metas.setdefault(ename, len(metas) + 1)
            evs.append(f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
                       f"duration_ps: {dur_ns * 1000} }}")
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    + " ".join(evs) + " }")
    meta = [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in metas.items()]
    return f'planes {{ name: "{name}" ' + " ".join(body + meta) + " }\n"


MM = "%approx_matmul_kernel_call.7 = s32[8,2048] custom-call(u8[8,2048] %a, u8[2048,2048] %b)"
PA = "%paged_attention_kernel_call.3 = f32[8,32,64] custom-call(s32[8,160] %t)"
HAND = (
    plane("/device:TPU:0", [
        ("XLA Modules", [("jit__decode_tick(1)", 100, 400),
                         ("jit__admit_fused_paged(2)", 600, 200)]),
        ("XLA Ops", [("%while.1 = (s32[]) while(...)", 100, 400),
                     (MM, 150, 100), (PA, 300, 50),
                     ("%fusion.9 = bf16[8,2048] fusion(...)", 600, 200)]),
    ])
    + plane("/host:CPU", [("python", [("bench.step", 50, 500),
                                      ("bench.submit", 560, 30),
                                      ("bench.step", 590, 310)])])
)


def test_hand_made_trace():
    t = load_text(HAND)
    assert t.window_s == pytest.approx(850e-9)             # spans 50..900
    assert t.busy_s == pytest.approx(600e-9)               # 100..500, 600..800
    assert t.module_seconds("_decode_tick") == pytest.approx(400e-9)
    assert t.module_seconds("_admit_fused_paged") == pytest.approx(200e-9)
    kernels = harness.kernel_costs()
    (mm,) = t.kernel_events(kernels["approx_matmul"])
    assert kernels["approx_matmul"].shapes(mm) == (8, 2048, 2048)
    assert len(t.kernel_events(kernels["paged_attention"])) == 1
    b = t.breakdown()
    ops = dict(b["device_ops"])
    assert ops["while (s32[])"] == pytest.approx(250e-9)    # 400 minus its children
    assert ops["approx_matmul_kernel_call s32[8,2048]"] == pytest.approx(100e-9)
    gaps = dict(b["idle_gaps"])
    # idle 50..100 and 800..900 fall in a bench.step; 500..600 has its middle
    # between the first step's end and the submit: a gap is named whole
    assert gaps["bench.step"] == pytest.approx(150e-9)
    assert gaps["no bench span"] == pytest.approx(100e-9)


def test_recorded_trace():
    t = load_text(FIXTURE.read_text())
    assert 0 < t.busy_s <= t.window_s
    kernels = harness.kernel_costs()
    mm = t.kernel_events(kernels["approx_matmul"])
    shapes = {kernels["approx_matmul"].shapes(e) for e in mm}
    # the admit's projections: q, k and v, o, gate and up, down
    assert shapes == {(2048, 2048, 2048), (2048, 2048, 512),
                      (2048, 2048, 8192), (2048, 8192, 2048)}
    assert not t.kernel_events(kernels["paged_attention"])
    b = t.breakdown()
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][0][0] == "approx_matmul_kernel_call s32[2048,8192]"
    # self times partition the busy time: nothing counted twice
    assert sum(s for _, s in tracing.self_times(t.ops)) == pytest.approx(
        t.busy_s, rel=0.05)
