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
ADMIT = "_admit_fused_paged"
BASE = program_trace.SCOPES

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


def record(pt, ticks: int, prefill_tokens: int = 0):
    """What the readers see of a run: the trace, read once, and its ticks
    and admitted prompt tokens."""
    trace = types.SimpleNamespace(
        counters0={"ticks": 0, "prefill_tokens": 0},
        counters1={"ticks": ticks, "prefill_tokens": prefill_tokens})
    return types.SimpleNamespace(trace=trace, _program_trace=pt)


def traced_run(directory, blob: bytes, reference, ticks: int):
    """A run whose trace ``program_trace.of`` has yet to read, as the
    harness hands it over: the trace file and the reference module."""
    (directory / "run.xplane.pb").write_bytes(blob)
    trace = types.SimpleNamespace(dir=directory, counters0={"ticks": 0},
                                  counters1={"ticks": ticks})
    return types.SimpleNamespace(trace=trace, reference=reference)


def reference_with_scopes(root, scopes):
    """A configuration's own reference module, as a new file would bring it,
    that declares ``scopes``."""
    (root / "references").mkdir(exist_ok=True)
    name = "hybrid_" + "_".join(scopes)
    (root / "references" / f"{name}.py").write_text(f"SCOPES = {scopes!r}\n")
    return harness.reference_module({"reference": name}, root)


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
    pt = program_trace.from_bytes(xspace(with_program()), BASE)
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
    after = program_trace.from_bytes(xspace(with_program()), BASE).data
    assert after.window_s == before.window_s
    assert after.busy_s == before.busy_s
    assert after.breakdown() == before.breakdown()


def test_readers_on_the_hand_made_trace():
    pt = program_trace.from_bytes(xspace(with_program()), BASE)
    names = ["decode_dense_ms", "decode_attention_ms", "decode_kv_write_ms",
             "decode_unscoped_ms", "host_admit_ms_per_step.offline",
             "host_accept_ms_per_step.offline", "admit_ms_per_ktok"]
    got = {n: m.read(record(pt, ticks=2, prefill_tokens=256))
           for n, m in harness.metric_readers(names).items()}
    assert got == pytest.approx({
        "decode_dense_ms": 1000 * 100e-9 / 2,
        "decode_attention_ms": 1000 * 50e-9 / 2,
        "decode_kv_write_ms": 0.0,
        "decode_unscoped_ms": 1000 * 250e-9 / 2,
        "host_admit_ms_per_step.offline": 1000 * 60e-9 / 2,
        "host_accept_ms_per_step.offline": 1000 * 100e-9 / 2,
        "admit_ms_per_ktok": 1000 * 200e-9 / 0.256})
    out = io.StringIO()
    program_trace.report(pt, record(pt, ticks=2), out)
    assert "idle gaps by program span (s): client 0.000000" in out.getvalue()
    assert "over 1 ms" not in out.getvalue()
    assert ("program scopes, _admit_fused_paged ms per call over 1 calls: "
            "embed 0.000, norm 0.000") in out.getvalue()


def test_program_scope_ms_counts_calls_not_ticks():
    """Any program by scope, per call of it: the admit's one module event,
    the decode tick's one against the two ticks ``decode_scope_ms`` counts."""
    pt = program_trace.from_bytes(xspace(with_program()), BASE)
    rec = record(pt, ticks=2)
    ms = program_trace.program_scope_ms
    assert ms(rec, ADMIT, "norm") == pytest.approx(1000 * 200e-9)
    assert ms(rec, ADMIT, None) == 0.0
    assert ms(rec, "_decode_tick", "dense") == pytest.approx(1000 * 100e-9)
    assert program_trace.decode_scope_ms(rec, "dense") == pytest.approx(
        1000 * 100e-9 / 2)
    assert ms(rec, "_prefill_chunk", "dense") is None      # never called


def test_admit_by_scope_over_two_calls():
    """Two admit calls around a decode tick, by hand: per call, each scope;
    per 1000 admitted prompt tokens, the whole module
    (``admit_ms_per_ktok``); and a stderr line for every program whose
    operations carry a scope, whatever its name, and none for one without."""
    admit, tick = "jit__admit_fused_paged(2)", "jit__decode_tick(1)"
    chunk, copy = "jit__prefill_chunk(3)", "jit__copy_block(4)"
    text = (fx.plane("/device:TPU:0", [
        ("XLA Modules", [(admit, 0, 300, {}), (tick, 300, 400, {}),
                         (admit, 500, 600, {}), (chunk, 600, 700, {}),
                         (copy, 700, 750, {})]),
        ("XLA Ops", [("%fusion.1 = bf16[2048,8192] fusion(...)", 0, 200, {}),
                     ("%fusion.2 = bf16[2048,512] fusion(...)", 200, 250, {}),
                     ("%copy.3 = bf16[2048,2048] copy(...)", 250, 300, {}),
                     ("%fusion.9 = bf16[8,8192] fusion(...)", 300, 400, {}),
                     ("%fusion.1 = bf16[128,8192] fusion(...)", 500, 580, {}),
                     ("%fusion.2 = bf16[128,512] fusion(...)", 580, 600, {}),
                     ("%fusion.4 = bf16[256,8192] fusion(...)", 600, 700, {}),
                     ("%copy.5 = bf16[16,512] copy(...)", 700, 750, {})])])
            + fx.span_plane("/host:CPU", [("bench.step", 0, 750, {})])
            + fx.metadata_plane({
                admit: fx.hlo_proto(admit, {
                    "fusion.1": "jit(_admit_fused_paged)/dense/dot",
                    "fusion.2": "jit(_admit_fused_paged)/attention/dot",
                    "copy.3": "jit(_admit_fused_paged)/while/body/copy"}),
                tick: fx.hlo_proto(tick, {
                    "fusion.9": "jit(_decode_tick)/dense/dot"}),
                chunk: fx.hlo_proto(chunk, {
                    "fusion.4": "jit(_prefill_chunk)/mlp/dot"}),
                copy: fx.hlo_proto(copy, {"copy.5": "jit(_copy_block)/copy"})}))
    pt = program_trace.from_bytes(xspace(text), BASE)
    rec = record(pt, ticks=1, prefill_tokens=640)
    by = {s: program_trace.program_scope_ms(rec, ADMIT, s)
          for s in ("dense", "attention", "kv_write", None)}
    assert by == pytest.approx({"dense": 1000 * 280e-9 / 2,
                                "attention": 1000 * 70e-9 / 2,
                                "kv_write": 0.0, None: 1000 * 50e-9 / 2})
    (reader,) = harness.metric_readers(["admit_ms_per_ktok"]).values()
    assert reader.read(rec) == pytest.approx(1000 * 400e-9 / 0.640)
    assert sum(by.values()) * 2 == pytest.approx(reader.read(rec) * 0.640)
    # no prompt token admitted in the traced window: nothing to divide by
    assert reader.read(record(pt, ticks=1)) is None
    out = io.StringIO()
    program_trace.report(pt, rec, out)
    lines = out.getvalue().splitlines()
    assert [ln.split(" ms per ")[0] for ln in lines
            if ln.startswith("program scopes")] == [
        "program scopes, _decode_tick", "program scopes, _admit_fused_paged",
        "program scopes, _prefill_chunk"]
    assert ("program scopes, _admit_fused_paged ms per call over 2 calls: "
            "embed 0.000, norm 0.000, attention 0.000") in out.getvalue()
    assert "program scopes, _prefill_chunk ms per call over 1 calls" in (
        out.getvalue())
    assert program_trace.program_of(copy) == "_copy_block"


def test_a_program_without_scopes_or_spans_reads_nothing():
    """The parent of the scopes: no scope on any op_name, no serve.* span."""
    bare = {m: {k: "" for k in ops} for m, ops in HLO.items()}
    for text in (HAND, with_program(hlo=bare, serve=[])):
        pt = program_trace.from_bytes(xspace(text), BASE)
        rec = record(pt, ticks=2)
        assert pt.scope_seconds("_decode_tick", None) is None
        assert program_trace.decode_scope_ms(rec, None) is None
        assert program_trace.program_scope_ms(rec, ADMIT, None) is None
        assert program_trace.span_ms_per_step(rec, "serve.admit") is None


def test_a_declared_scope_is_read_by_new_files_only(tmp_path):
    """A reference module that declares ``ssm`` and a plain reader file take
    the ops under ``.../ssm/...`` out of unscoped; ops under
    ``.../ssm/dense/...`` stay in ``dense``; without the declaration the
    first kind is unscoped, as with the base scopes alone."""
    hlo = {
        "jit__decode_tick(1)": {
            "while.1": "jit(_decode_tick)/while",
            "approx_matmul_kernel_call.7":
                f"{TICK}/ssm/dense/jit(approx_matmul_kernel_call)/"
                "approx_matmul_kernel_call/pallas_call",
            "paged_attention_kernel_call.3":
                f"{TICK}/ssm/jit(ssd_scan)/pallas_call",
        },
        "jit__admit_fused_paged(2)": {
            "fusion.9": "jit(_admit_fused_paged)/ssm/mul"},
    }
    blob = xspace(with_program(hlo=hlo))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "decode_ssm_ms.py").write_text(
        "from program_trace import decode_scope_ms\n\n\n"
        "def read(rec):\n    return decode_scope_ms(rec, \"ssm\")\n")
    ssm_reader = harness.metric_readers(["decode_ssm_ms"], tmp_path)
    readers = dict(harness.metric_readers(["decode_dense_ms",
                                           "decode_unscoped_ms"]), **ssm_reader)
    got = {}
    for scopes in (("ssm",), ()):
        run_dir = tmp_path / f"trace{len(scopes)}"
        run_dir.mkdir()
        rec = traced_run(run_dir, blob, reference_with_scopes(tmp_path, scopes),
                         ticks=2)
        got[scopes] = {n: m.read(rec) for n, m in readers.items()}
        got[scopes]["admit"] = program_trace.program_scope_ms(rec, ADMIT, "ssm")
        assert program_trace.of(rec).scopes == program_trace.SCOPES + scopes
    assert got[("ssm",)] == pytest.approx({
        "decode_ssm_ms": 1000 * 50e-9 / 2, "decode_dense_ms": 1000 * 100e-9 / 2,
        "decode_unscoped_ms": 1000 * 250e-9 / 2, "admit": 1000 * 200e-9})
    assert {n: v for n, v in got[()].items() if n != "admit"} == pytest.approx({
        "decode_ssm_ms": 0.0, "decode_dense_ms": 1000 * 100e-9 / 2,
        "decode_unscoped_ms": 1000 * 300e-9 / 2})
    # the admit's one op is then in no known scope: it carries none at all
    assert got[()]["admit"] is None


# the recorded tick as the seven base scopes read it, before a reference
# module could declare more; the dense module declares none
RECORDED_TICK = {
    "embed": 1.103e-06, "norm": 2.7696000000000017e-05,
    "attention": 0.00881059200000001, "kv_write": 6.320900000000005e-05,
    "dense": 0.01793303699999999, "mlp": 0.0, "sample": 2.522e-06,
    None: 0.029391683000000002}
RECORDED_READERS = {
    "decode_dense_ms": 17.933036999999988,
    "decode_attention_ms": 8.810592000000009,
    "decode_kv_write_ms": 0.06320900000000006,
    "decode_unscoped_ms": 29.391683}


def test_recorded_tick_reads_as_with_the_base_scopes(tmp_path):
    """Through ``of``, as a run reads it, with the dense reference module:
    every scope and every decode reader to the last digit."""
    dense = harness.reference_module(
        harness.load_config("granite-3-2b.exact"))
    rec = traced_run(tmp_path, xspace(FIXTURE.read_text()), dense, ticks=1)
    pt = program_trace.of(rec)
    assert pt.scopes == program_trace.SCOPES
    assert {s: pt.scope_seconds("_decode_tick", s)
            for s in RECORDED_TICK} == RECORDED_TICK
    assert {n: m.read(rec) for n, m in
            harness.metric_readers(RECORDED_READERS).items()} == RECORDED_READERS


def test_recorded_trace_scopes_partition_the_decode_tick():
    pt = program_trace.from_bytes(xspace(FIXTURE.read_text()), BASE)
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
