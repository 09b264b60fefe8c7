"""Compile-once detector API: CompiledDetector plan ownership + staleness,
DetectorSession streaming semantics (membrane carryover, reset()/state
contract, batch-of-sessions, mixed (1,3) schedule), and FrameRequest
serving through the Engine slot pool with executor parity vs the dense
oracle."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core import pruning
from repro.models import snn_yolo as sy
from repro.models.postprocess import Detections
from repro.serve import (
    AdmissionPolicy,
    CompiledDetector,
    DetectorEngineCore,
    Engine,
    EngineAPI,
    FrameRequest,
    LMEngineCore,
    StalePlanError,
)
from repro.serve.trace import Tracer


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(get_config("snn-det"))
    params, bn = sy.init_params(jax.random.PRNGKey(0), cfg)
    params = pruning.prune_tree(params, 0.8)
    rng = np.random.default_rng(0)
    h, w = cfg.input_hw
    # uint8-grid frames keep the bit-serial 8-bit encode path exact
    frames = jnp.asarray(rng.integers(0, 256, (6, 2, h, w, 3)) / 255.0, jnp.float32)
    # calibrated tdBN stats: fresh (0, 1) stats silence the deep layers of
    # an untrained net, which would make streaming tests vacuous
    bn = sy.calibrate_bn_state(params, bn, frames[0], cfg)
    return cfg, params, bn, frames


@pytest.fixture(scope="module")
def det(setup):
    cfg, params, bn, _ = setup
    return sy.compile_detector(
        dataclasses.replace(cfg, conv_exec="gated"), params, bn
    )


class TestCompiledDetector:
    def test_call_returns_detections(self, det, setup):
        _, _, _, frames = setup
        dets = det(frames[0])
        assert isinstance(dets, Detections)
        assert dets.boxes.shape[0] == 2 and dets.boxes.shape[-1] == 4
        assert dets.valid.dtype == jnp.bool_

    def test_plan_owned_and_stable(self, det, setup):
        _, _, _, frames = setup
        plan = det.plan
        assert plan is not None and plan.compressed_bytes < plan.dense_bytes
        det(frames[0])
        det(frames[1])
        assert det.plan is plan  # compiled once, never re-packed per call

    def test_dense_handle_owns_plan_and_float_handle_has_none(self, setup):
        """Quantized dense handles build the plan at compile time too —
        the dense executor reads its w_q/scale so every executor runs the
        same integer-domain math (the conformance suite's bit-exactness
        guarantee). Float handles have nothing to pack."""
        cfg, params, bn, frames = setup
        d = sy.compile_detector(cfg, params, bn)  # dense executor
        plan = d.plan
        assert plan is not None and plan.compressed_bytes < plan.dense_bytes
        d(frames[0])
        assert d.plan is plan  # compiled once, never re-packed
        f = sy.compile_detector(
            dataclasses.replace(cfg, weight_bits=0), params, bn
        )
        assert f.plan is None  # float weights: legacy fake-quant path
        f(frames[0])  # still serves

    def test_stale_params_raise(self, setup):
        cfg, params, bn, frames = setup
        d = sy.compile_detector(cfg, dict(params), bn)
        d(frames[0])
        # swap a weight leaf after compile: the owned plan no longer
        # describes the model -> every entry point must refuse
        d.params["encode"] = dict(d.params["encode"])
        d.params["encode"]["w"] = d.params["encode"]["w"] + 1e-3
        with pytest.raises(StalePlanError, match="compile"):
            d(frames[0])
        with pytest.raises(StalePlanError):
            d.detect(frames[0])

    def test_stale_params_raise_in_session(self, setup):
        cfg, params, bn, frames = setup
        d = sy.compile_detector(cfg, dict(params), bn)
        sess = d.new_session(batch=2)
        sess.step(frames[0])
        d.params["head"] = {"w": d.params["head"]["w"] * 2}
        with pytest.raises(StalePlanError):
            sess.step(frames[1])

    def test_forward_without_plan_raises(self, setup):
        """Migrated from the removed snn_yolo._cached_plan: the free
        function no longer auto-builds — plan ownership lives in the
        handle."""
        cfg, params, bn, frames = setup
        c = dataclasses.replace(cfg, conv_exec="pallas")
        with pytest.raises(ValueError, match="compile_detector"):
            sy.forward(params, bn, frames[0], c)

    def test_float_weights_cannot_compile_compressed(self, setup):
        cfg, params, bn, _ = setup
        c = dataclasses.replace(cfg, weight_bits=0, conv_exec="gated")
        with pytest.raises(ValueError, match="weight_bits"):
            sy.compile_detector(c, params, bn)

    def test_default_bn_state(self, setup):
        cfg, params, _, frames = setup
        d = sy.compile_detector(cfg, params)  # no bn given -> fresh stats
        assert set(d.bn_state) == {n for n in params if n != "head"}
        d(frames[0])  # runs


class TestDetectorSession:
    def test_cold_start_matches_stateless(self, det, setup):
        _, _, _, frames = setup
        sess = det.new_session(batch=2)
        step = sess.step(frames[0])
        dets, head = det.detect(frames[0])
        np.testing.assert_array_equal(np.asarray(step.head), np.asarray(head))
        np.testing.assert_array_equal(
            np.asarray(step.detections.scores), np.asarray(dets.scores)
        )

    def test_carryover_vs_fresh_parity_on_static_sequence(self, det, setup):
        """Replaying the same frame sequence from reset() reproduces the
        fresh session bit-exactly — carryover is a pure function of the
        streamed frames."""
        _, _, _, frames = setup
        sess = det.new_session(batch=2)
        heads_fresh = [np.asarray(sess.step(frames[0]).head) for _ in range(3)]
        sess.reset()
        heads_replay = [np.asarray(sess.step(frames[0]).head) for _ in range(3)]
        for a, b in zip(heads_fresh, heads_replay):
            np.testing.assert_array_equal(a, b)
        # and state genuinely flows: the warm second step differs from cold
        assert np.abs(heads_fresh[1] - heads_fresh[0]).max() > 0

    def test_reset_restores_cold_start_outputs(self, det, setup):
        _, _, _, frames = setup
        sess = det.new_session(batch=2)
        cold = np.asarray(sess.step(frames[0]).head)
        sess.step(frames[1])
        sess.step(frames[2])
        sess.reset()
        assert sess.frames_seen == 0
        np.testing.assert_array_equal(np.asarray(sess.step(frames[0]).head), cold)

    def test_state_contract(self, det, setup):
        _, _, _, frames = setup
        sess = det.new_session(batch=2)
        assert all(
            float(jnp.abs(v).max()) == 0.0
            for v in jax.tree_util.tree_leaves(sess.state)
        )
        assert "head" in sess.state  # the no-reset output accumulator
        sess.step(frames[0])
        assert any(
            float(jnp.abs(v).max()) > 0
            for v in jax.tree_util.tree_leaves(sess.state)
        )
        with pytest.raises(ValueError, match="batch"):
            sess.step(frames[0][:1])  # wrong batch size

    def test_batch_of_sessions_rows_independent(self, det, setup):
        """The vectorized path: row i of a batched session must equal an
        independent single-stream session fed row i's frames."""
        _, _, _, frames = setup
        batched = det.new_session(batch=2)
        outs = [np.asarray(batched.step(f).head) for f in frames[:3]]
        for row in range(2):
            solo = det.new_session(batch=1)
            for k, f in enumerate(frames[:3]):
                h = np.asarray(solo.step(f[row : row + 1]).head)
                np.testing.assert_array_equal(h[0], outs[k][row])

    def test_reset_out_of_range_raises(self, det):
        """Regression: jnp scatter drops OOB indices silently, so a typo'd
        stream index must fail loudly instead of resetting nothing."""
        sess = det.new_session(batch=2)
        with pytest.raises(IndexError, match="out of range"):
            sess.reset(2)
        sess.reset(-1)  # negative indices within range are fine

    def test_per_row_reset(self, det, setup):
        _, _, _, frames = setup
        sess = det.new_session(batch=2)
        cold = np.asarray(sess.step(frames[0]).head)
        warm = np.asarray(sess.step(frames[0]).head)
        sess.reset()
        sess.step(frames[0])
        sess.reset(0)  # row 0 cold, row 1 stays warm
        h = np.asarray(sess.step(frames[0]).head)
        np.testing.assert_array_equal(h[0], cold[0])
        np.testing.assert_array_equal(h[1], warm[1])

    @pytest.mark.parametrize("mixed", [True, False])
    def test_time_step_schedules(self, setup, mixed):
        """Both the paper's mixed (1, 3) schedule and the uniform-T
        baseline stream through the session path."""
        cfg, params, bn, frames = setup
        c = dataclasses.replace(cfg, conv_exec="gated", mixed_time=mixed)
        d = sy.compile_detector(c, params, bn)
        sess = d.new_session(batch=2)
        s1, s2 = sess.step(frames[0]), sess.step(frames[1])
        assert s1.head.shape == s2.head.shape
        assert bool(jnp.isfinite(s1.head).all() & jnp.isfinite(s2.head).all())
        _, head0 = d.detect(frames[0])
        np.testing.assert_array_equal(np.asarray(s1.head), np.asarray(head0))

    def test_non_snn_mode_has_no_sessions(self, setup):
        cfg, params, bn, _ = setup
        c = dataclasses.replace(cfg, mode="ann", conv_exec="dense")
        d = sy.compile_detector(c, params, bn)
        with pytest.raises(ValueError, match="mode"):
            d.new_session()


class TestFrameServing:
    """Acceptance: ≥8 concurrent FrameRequests through the slot pool, with
    compressed-executor outputs exactly matching the dense oracle."""

    N_REQUESTS, N_SLOTS, N_FRAMES = 9, 4, 2

    def _streams(self, cfg):
        rng = np.random.default_rng(7)
        h, w = cfg.input_hw
        return [
            (rng.integers(0, 256, (self.N_FRAMES, h, w, 3)) / 255.0).astype(np.float32)
            for _ in range(self.N_REQUESTS)
        ]

    @pytest.mark.parametrize("executor", ["gated", "pallas"])
    def test_slot_pool_matches_dense_oracle(self, setup, executor):
        cfg, params, bn, _ = setup
        streams = self._streams(cfg)
        d = sy.compile_detector(
            dataclasses.replace(cfg, conv_exec=executor), params, bn
        )
        eng = Engine(d, n_slots=self.N_SLOTS)
        reqs = [FrameRequest(rid=r, frames=s) for r, s in enumerate(streams)]
        for fr in reqs:
            eng.submit(fr)
        done = eng.run()
        assert len(done) == self.N_REQUESTS and all(r.done for r in done)
        assert all(len(r.out) == self.N_FRAMES for r in reqs)

        # oracle: each stream through its own dense sequential session
        dense = sy.compile_detector(
            dataclasses.replace(cfg, conv_exec="dense"), params, bn
        )
        for fr in reqs:
            solo = dense.new_session(batch=1)
            for f, served_head, served_dets in zip(fr.frames, fr.heads, fr.out):
                step = solo.step(f[None])
                # bit-exact: compressed executors share the dense oracle's
                # integer-domain math (tests/conformance/)
                np.testing.assert_array_equal(
                    served_head, np.asarray(step.head[0])
                )
                np.testing.assert_array_equal(
                    served_dets.valid, np.asarray(step.detections.valid[0])
                )

    def test_slot_reuse_and_admission(self, det, setup):
        cfg, _, _, _ = setup
        streams = self._streams(cfg)
        eng = Engine(det, n_slots=1)  # single slot recycled for every stream
        for r, s in enumerate(streams[:3]):
            eng.submit(FrameRequest(rid=r, frames=s))
        done = eng.run()
        assert [r.rid for r in done] == [0, 1, 2]

    def test_cores_satisfy_engine_api(self, det):
        assert isinstance(DetectorEngineCore(det, n_slots=2), EngineAPI)
        assert issubclass(LMEngineCore, object) and hasattr(LMEngineCore, "admit")

    def test_bad_frames_rejected_at_submit(self, det, setup):
        """Malformed requests get a typed rejection at submit — they never
        enter the queue, so the run loop never sees them."""
        _, _, _, frames = setup
        eng = Engine(det, n_slots=2)
        res = eng.submit(FrameRequest(rid=0, frames=np.zeros((8, 8, 3))))  # no F axis
        assert not res and not res.accepted
        assert "FrameRequest" in res.reason
        assert eng.queue == [] and eng.rejected[0].rid == 0
        out = eng.run()
        assert out.status == "drained" and len(out) == 0

    def test_mismatched_hw_rejected_before_touching_state(self, det, setup):
        """Regression: a FrameRequest whose H/W/channels don't match
        cfg.input_hw used to reset the slot's membrane and then explode
        later inside the batched step with an unrelated np.stack error.
        admit must validate FIRST and leave all state untouched."""
        cfg, _, _, frames = setup
        core = DetectorEngineCore(det, n_slots=2)
        h, w = cfg.input_hw
        good = FrameRequest(rid=0, frames=np.zeros((2, h, w, 3), np.float32))
        core.admit(good, 0)
        mem_before = jax.tree_util.tree_map(np.asarray, core._mem)
        rows_before = (dict(core._row_of), list(core._rows), set(core._cold))
        bad = FrameRequest(rid=1, frames=np.zeros((2, h + 2, w, 3), np.float32))
        with pytest.raises(ValueError, match="input_hw"):
            core.admit(bad, 1)
        assert (dict(core._row_of), list(core._rows), set(core._cold)) == rows_before
        for a, b in zip(
            jax.tree_util.tree_leaves(mem_before),
            jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(np.asarray, core._mem)
            ),
        ):
            np.testing.assert_array_equal(a, b)
        # wrong channel count is caught too
        with pytest.raises(ValueError, match="input_hw"):
            core.admit(
                FrameRequest(rid=2, frames=np.zeros((2, h, w, 1), np.float32)), 1
            )

    def test_engine_rejects_unknown_config(self):
        with pytest.raises(TypeError, match="serve"):
            Engine(object(), None)


class TestMegabatchServing:
    """The megabatched continuous-stream core: capacity buckets, join/leave
    row remapping, inactive-lane masking, double-buffered upload — all
    pinned bit-identical to independent single-stream DetectorSessions."""

    def _streams(self, cfg, lengths, seed=11):
        rng = np.random.default_rng(seed)
        h, w = cfg.input_hw
        return [
            (rng.integers(0, 256, (f, h, w, 3)) / 255.0).astype(np.float32)
            for f in lengths
        ]

    def _solo_replay(self, det, frames):
        solo = det.new_session(batch=1)
        return [np.asarray(solo.step(f[None]).head[0]) for f in frames]

    def test_join_leave_remap_parity_vs_solo_sessions(self, det, setup):
        """Staggered stream lengths + fewer slots than requests: every
        tick sees joins/leaves, rows swap-remove and the capacity bucket
        grows and shrinks — and every served head must STILL be
        bit-identical to an independent single-stream session replay."""
        cfg, _, _, _ = setup
        lengths = [1, 4, 2, 5, 3, 1, 2, 6, 1, 3]
        streams = self._streams(cfg, lengths)
        eng = Engine(det, n_slots=4)
        reqs = [FrameRequest(rid=r, frames=s) for r, s in enumerate(streams)]
        for fr in reqs:
            assert eng.submit(fr)
        out = eng.run()
        assert out.status == "drained" and len(out) == len(lengths)
        for fr in reqs:
            assert len(fr.heads) == len(fr.frames)
            for served, ref in zip(fr.heads, self._solo_replay(det, fr.frames)):
                np.testing.assert_array_equal(served, ref)

    def test_capacity_buckets_grow_and_shrink_without_losing_state(self, det, setup):
        """Crossing a bucket boundary (pad) and draining back down
        (shrink) must preserve resident rows bit-exactly."""
        cfg, _, _, _ = setup
        core = DetectorEngineCore(det, n_slots=16, min_bucket=2)
        assert core.cap == 2
        streams = self._streams(cfg, [6] * 5 + [2] * 2)
        reqs = [FrameRequest(rid=r, frames=s) for r, s in enumerate(streams)]
        # two long streams fill the min bucket...
        core.admit(reqs[0], 0)
        core.admit(reqs[1], 1)
        active = {0: reqs[0], 1: reqs[1]}
        core.step(active)
        assert core.cap == 2
        # ...then three more force growth 2 -> 4 -> 8
        for slot, r in [(2, reqs[2]), (3, reqs[3]), (4, reqs[4])]:
            core.admit(r, slot)
            active[slot] = r
        assert core.cap == 8
        while active:
            for slot in core.step(active):
                del active[slot]
        assert core.cap == 2  # drained back to the min bucket
        for fr in reqs[:5]:
            for served, ref in zip(fr.heads, self._solo_replay(det, fr.frames)):
                np.testing.assert_array_equal(served, ref)

    def test_inactive_lanes_masked_out_of_the_step(self, det, setup):
        """Satellite: dead bucket lanes must not evolve membrane between
        occupants, and active-row outputs must be bit-identical no matter
        what the dead lanes hold."""
        cfg, _, _, frames = setup
        mem = det.zero_state(4)
        active = np.array([True, True, False, False])
        batch = np.zeros((4,) + frames[0].shape[1:], np.float32)
        batch[:2] = np.asarray(frames[0])
        h1, m1, _ = det.masked_step(
            jnp.asarray(batch), mem, jnp.asarray(active)
        )
        # same active rows, garbage in the dead lanes
        garbage = batch.copy()
        garbage[2:] = 0.7
        h2, m2, _ = det.masked_step(
            jnp.asarray(garbage), mem, jnp.asarray(active)
        )
        np.testing.assert_array_equal(np.asarray(h1[:2]), np.asarray(h2[:2]))
        for a, b in zip(
            jax.tree_util.tree_leaves(m1), jax.tree_util.tree_leaves(m2)
        ):
            np.testing.assert_array_equal(np.asarray(a[:2]), np.asarray(b[:2]))
            # dead lanes: membrane frozen at its prior (zero) state
            assert float(jnp.abs(a[2:]).max()) == 0.0
            assert float(jnp.abs(b[2:]).max()) == 0.0

    def test_cold_mask_resets_a_dirty_lane_in_step(self, det, setup):
        """Satellite: the masked cold-start reset happens INSIDE the jitted
        step — a lane holding a retired stream's stale membrane must serve
        its new occupant bit-identically to an explicitly zeroed lane."""
        cfg, _, _, frames = setup
        batch = np.asarray(frames[0][:1])
        batch = np.concatenate([batch, batch], axis=0)  # rows 0 and 1 alike
        active = jnp.asarray(np.array([True, True]))
        no_cold = jnp.asarray(np.zeros(2, bool))
        # dirty both rows' membrane, then re-serve with row 1 marked cold
        _, dirty, _ = det.masked_step(
            jnp.asarray(batch), det.zero_state(2), active
        )
        h_cold, m_cold, _ = det.masked_step(
            jnp.asarray(batch), dirty, active,
            jnp.asarray(np.array([False, True])),
        )
        # reference: row 1 explicitly zeroed before the step
        zeroed = jax.tree_util.tree_map(lambda v: v.at[1].set(0.0), dirty)
        h_ref, m_ref, _ = det.masked_step(
            jnp.asarray(batch), zeroed, active, no_cold
        )
        np.testing.assert_array_equal(np.asarray(h_cold), np.asarray(h_ref))
        for a, b in zip(
            jax.tree_util.tree_leaves(m_cold), jax.tree_util.tree_leaves(m_ref)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_double_buffered_upload_changes_nothing(self, det, setup):
        """The staged next-tick upload is a pure latency optimization: a
        long steady-state stream (staging hits every tick) must serve
        bit-identically to the solo replay."""
        cfg, _, _, _ = setup
        (stream,) = self._streams(cfg, [6])
        eng = Engine(det, n_slots=2)
        fr = FrameRequest(rid=0, frames=stream)
        eng.submit(fr)
        eng.run()
        for served, ref in zip(fr.heads, self._solo_replay(det, stream)):
            np.testing.assert_array_equal(served, ref)

    def test_run_truncation_reports_pending(self, det, setup):
        """Satellite regression: run(max_steps) exhaustion used to drop
        queued and in-flight requests silently — they appeared in neither
        finished nor any error. Now the result says 'truncated' and lists
        every undone request, and a later run() resumes them."""
        cfg, _, _, _ = setup
        streams = self._streams(cfg, [4, 4, 4])
        eng = Engine(det, n_slots=2)
        reqs = [FrameRequest(rid=r, frames=s) for r, s in enumerate(streams)]
        for fr in reqs:
            eng.submit(fr)
        out = eng.run(max_steps=2)
        assert out.status == "truncated" and not out.drained
        assert len(out) == 0  # nothing finished in 2 ticks of 4-frame streams
        assert {r.rid for r in out.pending} == {0, 1, 2}
        assert all(not r.done for r in out.pending)
        # resume: in-flight slot state survived, everything drains
        out2 = eng.run()
        assert out2.status == "drained" and {r.rid for r in out2} == {0, 1, 2}
        for fr in reqs:  # and the interrupted run didn't corrupt anything
            for served, ref in zip(fr.heads, self._solo_replay(det, fr.frames)):
                np.testing.assert_array_equal(served, ref)

    def test_bounded_queue_rejects(self, det, setup):
        cfg, _, _, _ = setup
        streams = self._streams(cfg, [2] * 5)
        eng = Engine(
            det, n_slots=2, admission=AdmissionPolicy(max_queue=2)
        )
        results = [
            eng.submit(FrameRequest(rid=r, frames=s))
            for r, s in enumerate(streams)
        ]
        assert [bool(r) for r in results] == [True, True, False, False, False]
        assert all(r.reason == "queue-full" for r in results[2:])
        assert [r.rid for r in eng.rejected] == [2, 3, 4]
        out = eng.run()
        assert out.status == "drained" and {r.rid for r in out} == {0, 1}

    def test_shed_oldest_keeps_fresh_traffic(self, det, setup):
        cfg, _, _, _ = setup
        streams = self._streams(cfg, [2] * 5)
        eng = Engine(
            det,
            n_slots=2,
            admission=AdmissionPolicy(max_queue=2, on_full="shed-oldest"),
        )
        reqs = [FrameRequest(rid=r, frames=s) for r, s in enumerate(streams)]
        r0, r1 = eng.submit(reqs[0]), eng.submit(reqs[1])
        assert r0 and r1 and r0.shed == ()
        r2 = eng.submit(reqs[2])  # queue full: rid 0 (oldest) is shed
        assert r2 and r2.reason == "shed-oldest"
        assert tuple(r.rid for r in r2.shed) == (0,)
        assert [r.rid for r in eng.queue] == [1, 2]
        out = eng.run()
        assert {r.rid for r in out} == {1, 2}
        assert not reqs[0].done

    def test_admission_policy_validates(self):
        with pytest.raises(ValueError, match="on_full"):
            AdmissionPolicy(on_full="drop-newest")
        with pytest.raises(ValueError, match="max_queue"):
            AdmissionPolicy(max_queue=0)

    def test_step_latency_percentiles_over_synthetic_load(self, det, setup):
        """The tracer's summary over the load: tick percentiles in order,
        one ``tick`` and one ``block`` span per served tick, and the
        counters of ticks and frames."""
        cfg, _, _, _ = setup
        streams = self._streams(cfg, [3] * 6)
        eng = Engine(core=DetectorEngineCore(det, n_slots=4,
                                             tracer=Tracer(enabled=True)))
        for r, s in enumerate(streams):
            eng.submit(FrameRequest(rid=r, frames=s))
        eng.run()
        summary = eng.tracer.summary()
        tick = summary["spans"]["tick"]
        assert set(tick) == {"n", "p50_ms", "p95_ms", "total_ms"}
        assert 0 < tick["p50_ms"] <= tick["p95_ms"] <= tick["total_ms"]
        assert tick["n"] == summary["counters"]["ticks"] >= 3
        assert summary["spans"]["block"]["n"] == len(eng.core.step_wall) == tick["n"]
        assert summary["counters"]["frames"] == 6 * 3
        assert summary["spans"]["queued"]["n"] == summary["spans"]["admit"]["n"] == 6
