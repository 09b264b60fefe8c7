"""The serving path's tracer (``repro.serve.trace``): span nesting and ids,
the disabled default, the engine's spans per tick, the compile counter,
and the model's layer scopes (metadata only: the heads stay bit-identical)."""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core import pruning
from repro.models import snn_yolo as sy
from repro.serve import DetectorEngineCore, Engine, FrameRequest
from repro.serve import trace
from repro.serve.trace import Tracer

TICK_CHILDREN = ("assemble", "upload", "dispatch", "stage_next", "block",
                 "copy_out", "retire", "memory_stats")


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(get_config("snn-det"))
    params, bn = sy.init_params(jax.random.PRNGKey(0), cfg)
    params = pruning.prune_tree(params, 0.8)
    rng = np.random.default_rng(3)
    h, w = cfg.input_hw
    calib = (rng.integers(0, 256, (2, h, w, 3)) / 255.0).astype(np.float32)
    bn = sy.calibrate_bn_state(params, bn, calib, cfg)
    return cfg, params, bn


def _streams(cfg, lengths, seed=5):
    rng = np.random.default_rng(seed)
    h, w = cfg.input_hw
    return [(rng.integers(0, 256, (f, h, w, 3)) / 255.0).astype(np.float32)
            for f in lengths]


def _serve(det, streams, *, n_slots, tracer):
    eng = Engine(core=DetectorEngineCore(det, n_slots=n_slots, min_bucket=n_slots,
                                         tracer=tracer))
    reqs = [FrameRequest(rid=r, frames=s) for r, s in enumerate(streams)]
    for req in reqs:
        assert eng.submit(req)
    assert eng.run().drained
    return eng, reqs


def _children(spans, parent):
    return [s for s in spans if s.parent == parent]


# ----------------------------------------------------------------- tracer --


def test_spans_nest_with_parent_links_and_ids():
    t = Tracer(enabled=True)
    with t.span("tick", tick=0) as outer:
        with t.span("admit", rid=7):
            pass
        with t.span("block"):
            t.count("frames", 4)
    t.add("queued", 10, 20, rid=7)
    names = [s.name for s in t.spans]
    assert names == ["tick", "admit", "block", "queued"]
    tick, admit, block, queued = t.spans
    assert outer is tick
    assert (tick.parent, admit.parent, block.parent, queued.parent) == (-1, 0, 0, -1)
    assert tick.ids == {"tick": 0} and admit.ids == {"rid": 7} and queued.ids == {"rid": 7}
    assert tick.start_ns <= admit.start_ns <= admit.end_ns <= block.start_ns
    assert block.end_ns <= tick.end_ns
    assert (queued.start_ns, queued.end_ns) == (10, 20)
    assert t.counters == {"frames": 4}
    s = t.summary()
    assert s["spans"]["queued"] == {"n": 1, "p50_ms": 1e-5, "p95_ms": 1e-5, "total_ms": 1e-5}
    assert s["counters"] == {"frames": 4}


def test_disabled_tracer_records_nothing():
    t = Tracer()
    ctx = t.span("tick", tick=0)
    assert ctx is t.span("block") is trace.NULL.span("x")  # one shared no-op
    with ctx:
        t.count("frames")
        t.add("queued", 1, 2)
    t.sample_memory(jax.devices()[0])
    assert t.spans == [] and t.counters == {} and t.gauges == {}
    assert t.summary() == {"spans": {}, "counters": {}, "gauges": {}}


# ----------------------------------------------------------------- engine --


def test_engine_tick_spans_in_order_and_uploads_only_on_a_staged_miss(setup):
    cfg, params, bn = setup
    det = sy.compile_detector(dataclasses.replace(cfg, conv_exec="gated"), params, bn)
    tracer = Tracer(enabled=True)
    # two 3-frame streams and one of 1 frame: the short one's end remaps
    # the batch (a staged miss) while the others are mid-clip
    eng, reqs = _serve(det, _streams(cfg, [3, 3, 1]), n_slots=4, tracer=tracer)
    spans = tracer.spans
    ticks = [i for i, s in enumerate(spans) if s.name == "tick"]
    assert [spans[i].ids for i in ticks] == [{"tick": k} for k in range(len(ticks))]
    assert len(ticks) == tracer.counters["ticks"] == len(eng.core.step_wall) == 3
    assert tracer.counters["frames"] == 3 + 3 + 1
    misses = 0
    for i in ticks:
        kids = _children(spans, i)
        core = [s.name for s in kids if s.name != "admit"]
        assert set(core) <= set(TICK_CHILDREN)
        assert [n for n in TICK_CHILDREN if n in core] == core  # in order
        for name in ("dispatch", "block", "copy_out", "retire", "memory_stats"):
            assert core.count(name) == 1
        for a, b in zip(kids, kids[1:]):  # children do not overlap
            assert a.end_ns <= b.start_ns
        assert all(spans[i].start_ns <= k.start_ns <= k.end_ns <= spans[i].end_ns
                   for k in kids)
        if "assemble" in core:
            misses += 1
            assert core[:2] == ["assemble", "upload"]
        else:
            assert "upload" not in core
    # tick 0 admits (a miss), tick 1 finds its staged upload, tick 2
    # follows the short stream's end (a miss)
    assert misses == tracer.counters["sync_uploads"] == 2
    admits = [s for s in spans if s.name == "admit"]
    queued = [s for s in spans if s.name == "queued"]
    assert sorted(s.ids["rid"] for s in admits) == [r.rid for r in reqs]
    assert {s.ids["rid"] for s in queued} == {r.rid for r in reqs}
    assert all(s.parent == ticks[0] for s in admits)
    assert all(q.parent == -1 and q.end_ns <= spans[ticks[0]].end_ns for q in queued)


def test_compile_counter_counts_a_new_bucket_once(setup):
    cfg, params, bn = setup
    det = sy.compile_detector(dataclasses.replace(cfg, conv_exec="gated"), params, bn)
    first = Tracer(enabled=True)
    _serve(det, _streams(cfg, [2, 2]), n_slots=2, tracer=first)
    # the serving step at the new bucket, compiled or found in the
    # persistent cache
    assert first.counters.get("compiles", 0) + first.counters.get("cache_hits", 0) >= 1
    assert det._masked_step_fn._cache_size() == 1
    again = Tracer(enabled=True)
    _serve(det, _streams(cfg, [2, 2], seed=6), n_slots=2, tracer=again)
    assert again.counters.get("compiles", 0) == 0
    assert det._masked_step_fn._cache_size() == 1


# ----------------------------------------------------------------- scopes --


def test_layer_scopes_name_every_fused_layer_and_leave_heads_bit_identical(
        setup, monkeypatch):
    cfg, params, bn = setup
    cfg = dataclasses.replace(cfg, conv_exec="pallas")
    streams = _streams(cfg, [2, 2], seed=9)

    def heads_and_text():
        det = sy.compile_detector(cfg, params, bn)
        _, reqs = _serve(det, streams, n_slots=2, tracer=None)
        h, w = cfg.input_hw
        frames = jax.ShapeDtypeStruct((2, h, w, 3), np.float32)
        active = jax.ShapeDtypeStruct((2,), np.bool_)
        text = det._masked_step_fn.lower(det.params, det.bn_state, frames,
                                         det.zero_state(2), active, active).as_text(
                                             debug_info=True)
        return [np.stack(r.heads) for r in reqs], text

    scoped, text = heads_and_text()
    layers = sy.layer_specs(cfg)
    fused = [spec.name for spec in layers if spec.name != "head"]
    assert len(fused) == 27
    for name in fused + ["pool0", "head", "postprocess", "mask"]:
        assert f"/{name}/" in text, name
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain, plain_text = heads_and_text()
    assert "/encode/" not in plain_text
    for a, b in zip(scoped, plain):
        np.testing.assert_array_equal(a, b)
