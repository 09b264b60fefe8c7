"""CPU tests of the spans-and-scopes reduction (``trace_spans.py``), its
readers (``dispatch_ms``, ``upload_ms``, ``copyout_ms``, ``encode_ms``) and
``spans.py`` at the smoke size.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
import spans as spans_tool  # noqa: E402
import trace_reduce as tr  # noqa: E402
import trace_spans as ts  # noqa: E402
from test_bench import PEAKS, SPEC, hand_trace, smoke_cell  # noqa: E402

KERNEL = '%_dispatch_fused.1 = s8[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
COPY = "%copy.2 = f32[8]{0} copy(%fusion.1)"
SCOPES = ts.layer_scopes(["encode", "conv_block", "stage0/agg"])
OFFSET = 1_000_000  # the trace's profile_start_time on the spans' clock


def scoped_ops():
    """Two serving steps ([100, 400) and [600, 900); the third starts the
    window's end) with ops under known scopes, an unscoped one, and one that
    starts before the window."""
    ops = [(COPY, 0, 150, "jit(_masked)/encode/copy"),
           (KERNEL, 110, 210, "jit(_masked)/encode/jit(_dispatch_fused)/pallas_call"),
           (COPY, 220, 260, "jit(_masked)/encode/transpose"),
           (COPY, 270, 300, "jit(_masked)/stage0/agg/reshape"),
           (COPY, 300, 310, "jit(_masked)/stage0/concat/concatenate"),
           (KERNEL, 600, 700, "jit(_masked)/stage0/agg/jit(_dispatch_fused)/pallas_call"),
           (COPY, 750, 850, "jit(_masked)/mask/select_n"),
           (COPY, 860, 870, ""),
           (KERNEL, 1100, 1150, "jit(_masked)/encode/pallas_call")]
    modules = [("jit__masked(1)", 100, 400), ("jit__masked(1)", 600, 900),
               ("jit__masked(1)", 1100, 1200)]
    return ops, modules


def tick_spans():
    """Two ticks on the spans' clock (trace time + OFFSET) and a queued
    span that is not engine work."""
    o = OFFSET
    return [("tick", o + 50, o + 560, -1),
            ("dispatch", o + 60, o + 100, 0),
            ("block", o + 120, o + 420, 0),
            ("copy_out", o + 420, o + 480, 0),
            ("tick", o + 560, o + 950, -1),
            ("assemble", o + 560, o + 570, 4),
            ("upload", o + 570, o + 590, 4),
            ("dispatch", o + 590, o + 600, 4),
            ("block", o + 600, o + 910, 4),
            ("queued", o + 0, o + 2000, -1)]


def test_scope_of_takes_the_longest_known_prefix():
    assert ts.scope_of("jit(_masked)/stage0/agg/jit(_dispatch_fused)/pallas_call",
                       SCOPES) == "stage0/agg"
    assert ts.scope_of("jit(_masked)/stage0/concat/concatenate", SCOPES) == "stage0/concat"
    assert ts.scope_of("jit(_masked)/pool0/reduce_window_max", SCOPES) == "pool0"
    assert ts.scope_of("jit(_masked)/encoder/add", SCOPES) == ts.UNSCOPED
    assert ts.scope_of("", SCOPES) == ts.UNSCOPED


def test_op_paths_from_the_compiled_hlo_text():
    hlo = "\n".join([
        "ENTRY %main {",
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(_masked)/encode/mul"}',
        "  %copy.2 = f32[8]{0} copy(%fusion.1)",
        "  ROOT %copy.3 = f32[8]{0} copy(%copy.2)",
        "  %constant.4 = f32[] constant(0)",
        "}"])
    paths = ts.hlo_op_paths(hlo)
    # XLA's own copies take their operand's op_name; a constant has none
    assert paths == {"fusion.1": "jit(_masked)/encode/mul",
                     "copy.2": "jit(_masked)/encode/mul",
                     "copy.3": "jit(_masked)/encode/mul"}


def test_by_scope_per_step_kernels_and_glue_apart():
    ops, modules = scoped_ops()
    got = ts.by_scope(ops, modules, SCOPES)
    # per step over 2 steps; the copy that starts before the window and
    # the op of the last step are not counted
    assert got["encode"] == pytest.approx({"kernel_ms": 50e-6, "glue_ms": 20e-6})
    assert got["stage0/agg"] == pytest.approx({"kernel_ms": 50e-6, "glue_ms": 15e-6})
    assert got["stage0/concat"] == pytest.approx({"kernel_ms": 0.0, "glue_ms": 5e-6})
    assert got["mask"] == pytest.approx({"kernel_ms": 0.0, "glue_ms": 50e-6})
    assert got[ts.UNSCOPED] == pytest.approx({"kernel_ms": 0.0, "glue_ms": 5e-6})
    # the same ops as trace_reduce's kernel and glue split
    red = tr.reduce(tr.Events([o[:3] for o in ops], modules))
    assert sum(v["kernel_ms"] for v in got.values()) == pytest.approx(
        red["kernel_ns"] / red["n_steps"] / 1e6)
    assert sum(v["glue_ms"] for v in got.values()) == pytest.approx(
        red["other_ns"] / red["n_steps"] / 1e6)
    assert ts.by_scope(ops, modules[:1], SCOPES) == {}


def test_idle_gaps_split_over_the_innermost_engine_span():
    ops, modules = scoped_ops()
    busy = [(max(s, 100), min(e, 1100)) for _, s, e, _ in ops if e > 100 and s < 1100]
    gaps = tr._gaps(busy, 100, 1100)
    assert gaps == [(210, 220), (260, 270), (310, 600), (700, 750), (850, 860),
                    (870, 1100)]
    got = ts.idle_by_span(gaps, tick_spans(), OFFSET)
    # (210, 220), (260, 270), (700, 750) and (850, 860) inside block;
    # (310, 600): block to 420, copy_out to 480, the first tick's own time
    # to 560, then assemble 10, upload 20, dispatch 10; (870, 1100): block
    # to 910, the tick to 950, and 150 ns outside any tick (the queued span
    # is not engine work)
    assert got == pytest.approx({"block": 230e-9, "outside_engine": 150e-9,
                                 "tick": 120e-9, "copy_out": 60e-9,
                                 "upload": 20e-9, "assemble": 10e-9,
                                 "dispatch": 10e-9})
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in gaps) / 1e9)
    # without the profile's start time the gaps fall outside every span
    assert ts.idle_by_span(gaps, tick_spans(), 0) == pytest.approx(
        {"outside_engine": 600e-9})


def test_clock_check_steps_inside_dispatch_and_block():
    _, modules = scoped_ops()
    got = ts.clock_check(modules[:2], tick_spans(), OFFSET)
    assert got["steps"] == 2 and got["inside"] == 2 and got["share"] == 1.0
    # 420 − 400 and 910 − 900
    assert got["lag_ms_median"] == pytest.approx(15e-6)
    assert got["lag_ms_max"] == pytest.approx(20e-6)
    shifted = ts.clock_check(modules[:2], tick_spans(), OFFSET + 300)
    assert shifted["share"] == 0.0 and shifted["lag_ms_median"] is None


def test_new_readers_on_hand_built_spans_and_scopes():
    ops, modules = scoped_ops()
    ctx = {"trace": {**tr.empty(), "by_scope": ts.by_scope(ops, modules, SCOPES)},
           "spans": tick_spans(), "counters": {}}
    got = {name: run.load_reader(name)(ctx) for name in spans_tool.NEW_METRICS}
    assert got["dispatch_ms"] == pytest.approx((40 + 10) / 2 / 1e6)
    assert got["upload_ms"] == pytest.approx((10 + 20) / 2 / 1e6)
    assert got["copyout_ms"] == pytest.approx(60 / 2 / 1e6)
    assert got["encode_ms"] == pytest.approx(70e-6)
    empty = {"trace": tr.empty(), "spans": [], "counters": {}}
    assert all(run.load_reader(n)(empty) is None for n in spans_tool.NEW_METRICS)


def test_existing_reduction_unchanged_on_its_fixture():
    red = tr.reduce(hand_trace())
    assert set(red) == {"window_ns", "busy_ns", "n_steps", "step_busy_ns", "kernel_ns",
                        "other_ns", "top_ops", "idle_gaps"}
    assert (red["window_ns"], red["busy_ns"], red["n_steps"], red["kernel_ns"],
            red["other_ns"]) == (1000, 410, 2, 200, 210)
    assert red["step_busy_ns"] == [190, 210]


def test_traced_spans_keep_the_window_ticks_and_their_children():
    from repro.serve.trace import Tracer

    t = Tracer(enabled=True)
    for k in range(3):
        with t.span("tick", tick=k):
            with t.span("block"):
                pass
    t.add("queued", 0, 1, rid=0)
    got = spans_tool.traced_spans(t.spans, 1, 3)
    assert [(n, p) for n, _, _, p in got] == [("tick", -1), ("block", 0),
                                              ("tick", -1), ("block", 2)]


def test_measure_at_the_smoke_size():
    doc, traffic = smoke_cell("snn-det-mixed")
    out = spans_tool.measure(doc, traffic, 2**31 + 17, 0.5, PEAKS,
                             run.cell_metrics(SPEC["per_layer"], "mixed-fleet8"),
                             run.import_program(run.load_kind("detector")))
    assert len(out["tick_ms"]["off"]) == len(out["tick_ms"]["on"]) == 2
    # the CPU trace has no TPU plane: the device readings are empty, the
    # span readers read the program's spans
    assert {"dispatch_ms", "upload_ms", "copyout_ms"} <= set(out["metrics"])
    assert "encode_ms" not in out["metrics"]
    assert out["counters"].get("compiles", 0) == 0
    assert out["counters"]["ticks"] > 0
    assert out["summary_untraced"]["spans"]["tick"]["n"] > 0
