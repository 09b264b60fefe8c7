"""Cross-executor conformance: dense / gated / pallas must agree
BIT-EXACTLY through plan-compile → forward → decode → NMS, stateless and
streamed (DetectorSession membrane carryover), and must reproduce the
checked-in dense-oracle golden fixture.

Bit-exactness is by construction, not luck: every executor accumulates
binary spikes × int8 weights as integer-valued f32 (exact for any
summation order below 2^24) and applies the FXP scale once on the final
integer (core/plan.py). Downstream tdBN/LIF/decode/NMS is the one shared
jitted graph, so identical conv outputs imply identical everything.

Cross-executor assertions are exact (np.array_equal). Assertions against
the checked-in fixture are exact on structure (valid/classes) and
tight-tolerance on floats — float reductions inside tdBN may legitimately
reorder across XLA releases, and the fixture should catch semantic drift,
not compiler upgrades. Regenerate intentionally with
``PYTHONPATH=src python tests/conformance/make_golden.py``.
"""
from __future__ import annotations

import numpy as np
import pytest

import golden

GOLDEN_FLOAT_ATOL = 1e-5
COMPRESSED = [e for e in golden.EXECUTORS if e != "dense"]


@pytest.fixture(scope="module")
def inputs():
    return golden.build_inputs()


@pytest.fixture(scope="module")
def results(inputs):
    """Every executor's full conformance surface, computed once."""
    params, bn, frames = inputs
    return {
        ex: golden.run_executor(ex, params, bn, frames)
        for ex in golden.EXECUTORS
    }


@pytest.fixture(scope="module")
def checked_in():
    return golden.load_golden()


class TestCrossExecutorBitExact:
    @pytest.mark.parametrize("executor", COMPRESSED)
    def test_forward_head(self, results, executor):
        np.testing.assert_array_equal(
            results[executor]["head"], results["dense"]["head"]
        )

    @pytest.mark.parametrize("executor", COMPRESSED)
    def test_decode_nms_detections(self, results, executor):
        for field in ("boxes", "scores", "classes", "valid"):
            np.testing.assert_array_equal(
                results[executor][field], results["dense"][field],
                err_msg=f"{executor} diverges from dense on Detections.{field}",
            )

    @pytest.mark.parametrize("executor", COMPRESSED)
    def test_streamed_session_heads(self, results, executor):
        """Membrane carryover: every streamed frame's head is bit-equal,
        so state drift cannot accumulate silently across a video."""
        for k in range(golden.N_FRAMES):
            np.testing.assert_array_equal(
                results[executor][f"stream_head_{k}"],
                results["dense"][f"stream_head_{k}"],
                err_msg=f"{executor} drifts from dense at streamed frame {k}",
            )

    @pytest.mark.parametrize("executor", COMPRESSED)
    def test_final_membrane_state(self, results, executor):
        mem_keys = [k for k in results["dense"] if k.startswith("mem/")]
        assert mem_keys, "dense reference exposes no membrane state"
        for k in mem_keys:
            np.testing.assert_array_equal(
                results[executor][k], results["dense"][k],
                err_msg=f"{executor} membrane state {k} diverges",
            )


class TestAgainstCheckedInGolden:
    def test_fixture_inputs_match(self, inputs, checked_in):
        """The deterministic frame stream is reproduced bit-exactly —
        if this fails, the data/seed pipeline changed, not the executors."""
        _, _, frames = inputs
        np.testing.assert_array_equal(np.asarray(frames), checked_in["frames"])

    @pytest.mark.parametrize("executor", list(golden.EXECUTORS))
    def test_against_golden(self, results, checked_in, executor):
        got = results[executor]
        for k, want in checked_in.items():
            if k == "frames":
                continue
            assert k in got, f"missing conformance surface {k!r}"
            if want.dtype.kind in "fc":
                np.testing.assert_allclose(
                    got[k], want, atol=GOLDEN_FLOAT_ATOL, rtol=0,
                    err_msg=f"{executor} drifts from golden on {k}",
                )
            else:
                np.testing.assert_array_equal(
                    got[k], want, err_msg=f"{executor} drifts from golden on {k}"
                )

    def test_membrane_pytree_structure_stable(self, results, checked_in):
        """The DetectorSession state contract: same layer keys as the
        golden (a renamed/dropped membrane leaf breaks stream resume)."""
        want = {k for k in checked_in if k.startswith("mem/")}
        got = {k for k in results["dense"] if k.startswith("mem/")}
        assert got == want


class TestSessionContract:
    """Streaming semantics, asserted per executor (satellite: membrane
    carry across frames differs from reset streams; batched rows evolve
    independently)."""

    @pytest.mark.parametrize("executor", list(golden.EXECUTORS))
    def test_carry_differs_from_reset_stream(self, inputs, executor):
        import dataclasses

        from repro.models import snn_yolo as sy

        params, bn, frames = inputs
        cfg = dataclasses.replace(
            golden.conformance_config(), conv_exec=executor
        )
        det = sy.compile_detector(cfg, params, bn)
        carry = det.new_session(batch=golden.BATCH)
        reset = det.new_session(batch=golden.BATCH)
        h_carry, h_reset = [], []
        for k in range(golden.N_FRAMES):
            h_carry.append(np.asarray(carry.step(frames[k]).head))
            reset.reset()
            h_reset.append(np.asarray(reset.step(frames[k]).head))
        # frame 0: cold state on both paths -> identical
        np.testing.assert_array_equal(h_carry[0], h_reset[0])
        # later frames: warm membrane must actually matter
        assert any(
            np.abs(a - b).max() > 0 for a, b in zip(h_carry[1:], h_reset[1:])
        ), "membrane carryover had no effect — streaming state is dead"

    @pytest.mark.parametrize("executor", list(golden.EXECUTORS))
    def test_batched_rows_evolve_independently(self, inputs, executor):
        import dataclasses

        from repro.models import snn_yolo as sy

        params, bn, frames = inputs
        cfg = dataclasses.replace(
            golden.conformance_config(), conv_exec=executor
        )
        det = sy.compile_detector(cfg, params, bn)
        # row 0 streams frames in order, row 1 in reverse: rows see
        # different histories, so their states must not mix
        batched = det.new_session(batch=2)
        seq0 = [frames[k][0:1] for k in range(golden.N_FRAMES)]
        seq1 = [frames[golden.N_FRAMES - 1 - k][1:2] for k in range(golden.N_FRAMES)]
        outs = [
            np.asarray(batched.step(np.concatenate([a, b], axis=0)).head)
            for a, b in zip(seq0, seq1)
        ]
        for row, seq in ((0, seq0), (1, seq1)):
            solo = det.new_session(batch=1)
            for k, f in enumerate(seq):
                h = np.asarray(solo.step(f).head)
                np.testing.assert_array_equal(
                    h[0], outs[k][row],
                    err_msg=f"{executor} row {row} state mixed at frame {k}",
                )


class TestFusedPathActive:
    """The pallas rows above must test the FUSED pipeline, not a silent
    fallback: the compiled step's trace must contain exactly one
    pallas_call per fused-eligible (conv+tdBN+LIF) layer — encode's 8 bit-
    serial planes fold into its single lane-dense dispatch, and the
    pointwise head (no tdBN/LIF to fuse) contracts outside the kernel."""

    def test_one_dispatch_per_fused_layer(self, inputs):
        import dataclasses

        from repro.kernels import backend
        from repro.models import snn_yolo as sy

        params, bn, frames = inputs
        cfg = dataclasses.replace(
            golden.conformance_config(), conv_exec="pallas"
        )
        det = sy.compile_detector(cfg, params, bn)
        fused_layers = [n for n in det.plan.layers if "gamma" in params[n]]
        assert fused_layers, "no fused-eligible layers — config degenerate"
        n_calls = backend.count_pallas_calls(
            lambda f: det._step(det.params, det.bn_state, f, None)[0],
            frames[0],
        )
        assert n_calls == len(fused_layers), (
            f"pallas step traced {n_calls} pallas_calls for "
            f"{len(fused_layers)} fused-eligible layers — the fused "
            "pipeline is not one-dispatch-per-layer"
        )
