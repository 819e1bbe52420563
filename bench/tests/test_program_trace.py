"""The program's own marks in a trace (``program_trace.py``): on the hand-made
trace of ``test_tracing.py`` with the program's scopes and ``serve.*`` spans
added, whose answers are known exactly, and on a trace recorded on a TPU v5e
(one decode tick of the approx offline cell, cut by
``make_scope_fixture.py``)."""
import io
import pathlib
import types

import jax
import pytest

import harness
import make_scope_fixture as fx
import program_trace
import tracing
from test_tracing import HAND

FIXTURE = pathlib.Path(__file__).parent / "scope_fixture.pbtxt"

TICK = "jit(_decode_tick)/while/body/closed_call"
HLO = {
    "jit__decode_tick(1)": {
        "while.1": "jit(_decode_tick)/while",
        "approx_matmul_kernel_call.7":
            f"{TICK}/attention/dense/jit(approx_matmul_kernel_call)/"
            "approx_matmul_kernel_call/pallas_call",
        "paged_attention_kernel_call.3":
            f"{TICK}/attention/jit(paged_attention_kernel_call)/pallas_call",
    },
    "jit__admit_fused_paged(2)": {"fusion.9": "jit(_admit_fused_paged)/norm/mul"},
}
# (name, start ns, end ns, stats); the two steps' children never overlap
SERVE = [
    ("serve.step", 60, 540, {"clock": 4}), ("serve.admit", 70, 90, {}),
    ("serve.dispatch", 90, 110, {}), ("serve.harvest_wait", 110, 510, {}),
    ("serve.accept", 510, 530, {}),
    ("serve.step", 595, 895, {"clock": 5}), ("serve.admit", 600, 700, {}),
    ("serve.harvest_wait", 620, 680, {}), ("serve.dispatch", 700, 720, {}),
    ("serve.accept", 800, 880, {}),
]


def xspace(text: str) -> bytes:
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(text)


def with_program(hlo=HLO, serve=SERVE) -> str:
    protos = {m: fx.hlo_proto(m, ops) for m, ops in hlo.items()}
    return HAND + fx.span_plane("/host:CPU", serve) + fx.metadata_plane(protos)


def record(pt, ticks: int):
    """What the readers see of a run: the trace, read once, and its ticks."""
    trace = types.SimpleNamespace(counters0={"ticks": 0},
                                  counters1={"ticks": ticks})
    return types.SimpleNamespace(trace=trace, _program_trace=pt)


def test_wire_format_round_trip():
    blob = fx.hlo_proto("m", {"fusion.3": "jit(f)/dense/dot", "copy.1": ""})
    assert program_trace.op_names(blob) == {"fusion.3": "jit(f)/dense/dot",
                                            "copy.1": ""}
    assert program_trace.innermost_scope("jit(f)/vmap(sample)/argmax") == "sample"
    assert program_trace.innermost_scope(
        "transpose(jvp(dense))/mlp/jit(silu)/logistic") == "mlp"
    assert program_trace.innermost_scope("jit(f)/while/body/copy") is None
    assert program_trace.instruction("%copy.61 = bf16[1,2] copy(...)") == "copy.61"


def test_hand_made_trace_with_scopes_and_spans():
    pt = program_trace.from_bytes(xspace(with_program()))
    # device: the decode tick's 400 ns split by innermost scope
    assert pt.scope_seconds("_decode_tick", "dense") == pytest.approx(100e-9)
    assert pt.scope_seconds("_decode_tick", "attention") == pytest.approx(50e-9)
    assert pt.scope_seconds("_decode_tick", None) == pytest.approx(250e-9)
    assert pt.scope_seconds("_decode_tick", "kv_write") == 0.0
    assert pt.scope_seconds("_admit_fused_paged", "norm") == pytest.approx(200e-9)
    parts = [pt.scope_seconds("_decode_tick", s)
             for s in program_trace.SCOPES + (None,)]
    assert sum(parts) == pytest.approx(pt.data.module_seconds("_decode_tick"))
    # host: self time of each span name, nested harvest_wait taken out
    own = {n: pt.span_self_seconds(n) for n in
           ("serve.step", "serve.admit", "serve.dispatch",
            "serve.harvest_wait", "serve.accept")}
    assert own == pytest.approx({"serve.step": 120e-9, "serve.admit": 60e-9,
                                 "serve.dispatch": 40e-9,
                                 "serve.harvest_wait": 460e-9,
                                 "serve.accept": 100e-9})
    assert len(pt.steps()) == 2
    # idle 50..100 falls in serve.admit, 500..600 between the two steps,
    # 800..900 in serve.accept
    gaps = [(round(at * 1e9), round(d * 1e9), n) for at, d, n in pt.idle_gaps()]
    assert gaps == [(0, 50, "serve.admit"), (450, 100, program_trace.CLIENT),
                    (750, 100, "serve.accept")]


def test_serve_spans_leave_the_window_as_it_was():
    """``bench.*`` spans define the window: adding the program's spans and
    HLO changes nothing ``tracing.py`` reports."""
    before = tracing.from_profile(jax.profiler.ProfileData.from_text_proto(HAND))
    after = program_trace.from_bytes(xspace(with_program())).data
    assert after.window_s == before.window_s
    assert after.busy_s == before.busy_s
    assert after.breakdown() == before.breakdown()


def test_readers_on_the_hand_made_trace():
    pt = program_trace.from_bytes(xspace(with_program()))
    names = ["decode_dense_ms", "decode_attention_ms", "decode_kv_write_ms",
             "decode_unscoped_ms", "host_admit_ms_per_step.offline",
             "host_accept_ms_per_step.offline"]
    got = {n: m.read(record(pt, ticks=2))
           for n, m in harness.metric_readers(names).items()}
    assert got == pytest.approx({
        "decode_dense_ms": 1000 * 100e-9 / 2,
        "decode_attention_ms": 1000 * 50e-9 / 2,
        "decode_kv_write_ms": 0.0,
        "decode_unscoped_ms": 1000 * 250e-9 / 2,
        "host_admit_ms_per_step.offline": 1000 * 60e-9 / 2,
        "host_accept_ms_per_step.offline": 1000 * 100e-9 / 2})
    out = io.StringIO()
    program_trace.report(pt, record(pt, ticks=2), out)
    assert "idle gaps by program span (s): client 0.000000" in out.getvalue()
    assert "over 1 ms" not in out.getvalue()


def test_a_program_without_scopes_or_spans_reads_nothing():
    """The parent of the scopes: no scope on any op_name, no serve.* span."""
    bare = {m: {k: "" for k in ops} for m, ops in HLO.items()}
    for text in (HAND, with_program(hlo=bare, serve=[])):
        pt = program_trace.from_bytes(xspace(text))
        rec = record(pt, ticks=2)
        assert pt.scope_seconds("_decode_tick", None) is None
        assert program_trace.decode_scope_ms(rec, None) is None
        assert program_trace.span_ms_per_step(rec, "serve.admit") is None


def test_recorded_trace_scopes_partition_the_decode_tick():
    pt = program_trace.from_bytes(xspace(FIXTURE.read_text()))
    mods = [m for m in pt.data.modules if "_decode_tick" in m.name]
    assert len(mods) == 1
    parts = {s: pt.scope_seconds("_decode_tick", s)
             for s in program_trace.SCOPES + (None,)}
    assert all(parts[s] > 0 for s in ("dense", "attention", "kv_write", None))
    # every op of the tick belongs to one scope or to none, and together they
    # cover the module's device time
    assert sum(parts.values()) == pytest.approx(
        pt.data.module_seconds("_decode_tick"), rel=0.02)
    kernels = harness.kernel_costs()
    assert pt.data.kernel_events(kernels["approx_matmul"])
    assert pt.data.kernel_events(kernels["paged_attention"])
    # the host's serve.* spans lie on the device's clock, inside the tick
    # they overlap: the next tick's dispatch runs while this one computes
    assert "serve.dispatch" in {e.name for e in pt.program_spans}
    assert not [g for g in pt.idle_gaps() if g[1] > 1e-3]
