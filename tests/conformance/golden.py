"""Shared builders for the cross-executor conformance suite.

One frozen small config + seeded weights + seeded frame stream, and the
dense-oracle reference outputs for them. ``make_golden.py`` serializes the
reference to ``fixtures/golden_conformance.npz`` (checked in);
``test_conformance.py`` asserts every executor reproduces it and that all
executors agree bit-exactly among themselves.

Regenerate (only when the detector's semantics intentionally change):

    PYTHONPATH=src python tests/conformance/make_golden.py
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core import pruning
from repro.models import snn_yolo as sy

EXECUTORS = ("dense", "gated", "pallas")
SEED = 0
PRUNE_RATE = 0.8
N_FRAMES = 3
BATCH = 2
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "golden_conformance.npz")


def conformance_config() -> sy.SNNDetConfig:
    """Smoke-scale paper topology. use_block_conv=True is REQUIRED for
    conformance: the gated and Pallas executors always use block-conv
    border semantics, so the dense oracle must too."""
    return dataclasses.replace(
        smoke_config(get_config("snn-det")), arch_id="snn-det-conformance",
        use_block_conv=True,
    )


def build_inputs(cfg: sy.SNNDetConfig | None = None):
    """Deterministic (params, bn, frames): pruned seeded weights, tdBN
    calibrated on the first frame, uint8-grid frames (exact under the
    bit-serial 8-bit encode path). frames: (N_FRAMES, BATCH, H, W, 3)."""
    cfg = cfg or conformance_config()
    params, bn = sy.init_params(jax.random.PRNGKey(SEED), cfg)
    params = pruning.prune_tree(params, PRUNE_RATE)
    rng = np.random.default_rng(SEED)
    h, w = cfg.input_hw
    frames = jnp.asarray(
        rng.integers(0, 256, (N_FRAMES, BATCH, h, w, 3)) / 255.0, jnp.float32
    )
    bn = sy.calibrate_bn_state(params, bn, frames[0], cfg)
    return params, bn, frames


def run_executor(executor: str, params, bn, frames, cfg=None) -> dict:
    """The full conformance surface for one executor: plan-compile →
    stateless forward → decode → NMS, plus a streamed session (membrane
    carryover across N_FRAMES) and its final state."""
    cfg = dataclasses.replace(cfg or conformance_config(), conv_exec=executor)
    det = sy.compile_detector(cfg, params, bn)
    dets, head = det.detect(frames[0])
    out = {
        "head": np.asarray(head),
        "boxes": np.asarray(dets.boxes),
        "scores": np.asarray(dets.scores),
        "classes": np.asarray(dets.classes),
        "valid": np.asarray(dets.valid),
    }
    sess = det.new_session(batch=BATCH)
    for k in range(N_FRAMES):
        step = sess.step(frames[k])
        out[f"stream_head_{k}"] = np.asarray(step.head)
        out[f"stream_valid_{k}"] = np.asarray(step.detections.valid)
    for name, v in sess.state.items():
        # compared in NHWC element order: the pallas executor's encode leaf
        # is lane-dense, (N, H, W/bw, bw·C)
        v = np.asarray(v)
        c = params[name]["w"].shape[-1]
        out[f"mem/{name}"] = v.reshape(v.shape[:2] + (-1, c))
    return out


def build_reference() -> dict:
    """The full fixture payload: dense-oracle outputs + the input frames.
    THE one recipe — ``make_golden.py`` (write) and
    ``scripts/regen_goldens.py`` (write + --check) both call this, so the
    two entry points can never drift apart."""
    params, bn, frames = build_inputs()
    ref = run_executor("dense", params, bn, frames)
    ref["frames"] = np.asarray(frames)
    return ref


def load_golden() -> dict:
    with np.load(FIXTURE) as z:
        return {k: z[k] for k in z.files}
