"""Compile-once detector serving: handles, streaming sessions, slot core.

The paper's accelerator is a compile-once pipeline — weights are pruned,
FXP8-quantized and bitmask-compressed offline, then frames stream through.
This module is that shape as an API:

* :class:`CompiledDetector` — the compile-once handle. Owns the
  :class:`~repro.core.plan.DetectorPlan` (built exactly once, staleness-
  checked on every call), the jitted executor-backed forward, and the
  postprocess stage (``decode_head`` → score threshold → class-aware NMS),
  so callers go ``det = compile_detector(cfg, params); dets = det(frames)``
  with zero plan plumbing.

* :class:`DetectorSession` — a streaming handle over consecutive video
  frames. Carries every LIF membrane potential (and the head accumulator)
  across frames — warm-starting temporal state instead of re-zeroing per
  frame — with an explicit ``reset()``/``state`` contract. One session
  object vectorizes a whole batch of independent streams (row i of the
  batch is stream i; ``reset(i)`` cold-starts just that row), which is what
  the serve Engine's slot pool runs on.

* :class:`FrameRequest` + :class:`DetectorEngineCore` — the detector
  backend for the Engine's slot/admission loop (``EngineAPI``): continuous
  batching of frame streams over detector slots, one batched session step
  per engine tick.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as cplan
from repro.core import pruning
from repro.models import snn_yolo as sy
from repro.models.postprocess import Detections, postprocess
from repro.serve.trace import NULL as NULL_TRACER
from repro.serve.trace import Tracer


class StalePlanError(RuntimeError):
    """The handle's params changed after compile — its plan (and the jitted
    closure over it) no longer describe the weights. Re-run
    ``compile_detector`` on the new params."""


def _weight_leaves(params) -> tuple:
    return tuple(layer_p["w"] for layer_p in params.values())


def _affine_input_leaves(params, bn_state) -> tuple:
    """Every leaf the precomputed fused-kernel affine bundles were built
    from (gamma/beta + calibrated BN mean/var) — fingerprinted alongside
    the weights so a post-compile swap of any of them is refused instead of
    silently serving stale normalization constants."""
    leaves = []
    for name in sorted(params):
        p = params[name]
        if "gamma" not in p or name not in (bn_state or {}):
            continue
        st = bn_state[name]
        leaves += [p["gamma"], p["beta"], st["mean"], st["var"]]
    return tuple(leaves)


class SessionStep(NamedTuple):
    """One streamed frame's outputs: postprocessed detections + raw head."""

    detections: Detections
    head: jax.Array  # (N, gh, gw, A, 5+C) raw predictions


class CompiledDetector:
    """Compile-once handle around the detector.

    Build through :func:`repro.models.snn_yolo.compile_detector`. The
    constructor prunes (optionally), builds the compression plan ONCE, and
    jits a single step function — forward through the configured conv
    executor plus the full postprocess — that every call and every session
    reuses. ``__call__`` is stateless (cold membrane per frame);
    :meth:`new_session` returns the streaming handle.
    """

    def __init__(
        self,
        cfg: sy.SNNDetConfig,
        params,
        bn_state=None,
        *,
        anchors=sy.DEFAULT_ANCHORS,
        score_threshold: float = 0.25,
        iou_threshold: float = 0.5,
        max_detections: int = 32,
        prune_rate: float | None = None,
    ):
        if prune_rate is not None:
            params = pruning.prune_tree(params, prune_rate)
        self.cfg = cfg
        self.params = params
        self.bn_state = bn_state if bn_state is not None else sy.default_bn_state(params)
        self.anchors = tuple(anchors)
        self.score_threshold = float(score_threshold)
        self.iou_threshold = float(iou_threshold)
        self.max_detections = int(max_detections)
        if cfg.conv_exec != "dense" and not cfg.weight_bits:
            raise ValueError(
                f"conv_exec={cfg.conv_exec!r} requires weight_bits > 0; "
                "float weights only run through the dense oracle"
            )
        # the compile step: one pass over the tree. The plan is the handle's
        # owned artifact, built for EVERY quantized handle — dense included:
        # the dense executor consumes the plan's w_q/scale so all three
        # executors run the same integer-domain accumulate-then-scale math
        # and agree bit-exactly (tests/conformance/ asserts it). Only
        # weight_bits=0 (float) handles have nothing to pack and keep the
        # legacy fake-quant float path.
        self._plan = cplan.build_plan(params, cfg) if cfg.weight_bits else None
        # staleness fingerprint: identity of every weight leaf at compile
        # time. A swapped/mutated leaf means the packed plan and the jitted
        # constants are lying about the model -> refuse loudly.
        self._compiled_leaves = _weight_leaves(params)

        # compile-once affine hoist (pallas executor): the fused kernel's
        # per-layer parameter bundle depends only on weights + calibrated BN
        # stats, so build the whole set here instead of re-deriving it from
        # gamma/beta/mean/var on EVERY frame — those ops sit right before a
        # pallas_call and can't fuse into it. The bundle inputs join the
        # staleness fingerprint (check_plan) so a post-compile swap of
        # bn_state or gamma/beta fails loudly rather than serving stale
        # constants.
        self._affines = None
        self._affine_leaves: tuple = ()
        if self._plan is not None and cfg.conv_exec == "pallas" and cfg.mode == "snn":
            self._affines = cplan.precompute_affines(
                self._plan, params, self.bn_state, cfg
            )
            self._affine_leaves = _affine_input_leaves(params, self.bn_state)

        cfg_, plan_, affines_ = cfg, self._plan, self._affines

        def _step(params, bn, frames, mem):
            head, _, aux = sy.forward(
                params, bn, frames, cfg_, train=False, plan=plan_, membrane=mem,
                affines=affines_,
            )
            with jax.named_scope("postprocess"):
                dets = postprocess(
                    head,
                    self.anchors,
                    score_threshold=self.score_threshold,
                    iou_threshold=self.iou_threshold,
                    max_detections=self.max_detections,
                )
            return head, aux["membrane"], dets

        def _masked(params, bn, frames, mem, active, cold):
            # masked cold-start reset: rows joining this tick start from a
            # zero membrane INSIDE the jitted step — admission never issues
            # eager per-leaf device scatters
            def blank(v):
                m = cold.reshape((-1,) + (1,) * (v.ndim - 1))
                return jnp.where(m, jnp.zeros((), v.dtype), v)

            with jax.named_scope("mask"):
                mem0 = jax.tree_util.tree_map(blank, mem)
            head, new_mem, dets = _step(params, bn, frames, mem0)

            # inactive rows are dead lanes in the megabatch: their compute
            # is discarded and their membrane must NOT evolve between
            # occupants — keep the old state wherever active is False
            def keep(new, old):
                m = active.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(m, new, old)

            with jax.named_scope("mask"):
                new_mem = jax.tree_util.tree_map(keep, new_mem, mem0)
            return head, new_mem, dets

        self._step = jax.jit(_step)
        self._masked_step_fn = jax.jit(_masked)

    @property
    def plan(self):
        """The owned DetectorPlan, built exactly once at compile time.
        None only when weight_bits=0 (float weights: nothing to compress,
        and the forward runs the legacy fake-quant path)."""
        return self._plan

    # ------------------------------------------------------------- checks --
    def check_plan(self) -> None:
        """Raise :class:`StalePlanError` if params changed after compile."""
        now = _weight_leaves(self.params)
        if len(now) != len(self._compiled_leaves) or any(
            a is not b for a, b in zip(now, self._compiled_leaves)
        ):
            raise StalePlanError(
                "detector params changed after compile: the owned plan/jit "
                "no longer match the weights — call "
                "snn_yolo.compile_detector(cfg, params) again"
            )
        if self._affines is not None:
            now_aff = _affine_input_leaves(self.params, self.bn_state)
            if len(now_aff) != len(self._affine_leaves) or any(
                a is not b for a, b in zip(now_aff, self._affine_leaves)
            ):
                raise StalePlanError(
                    "detector BN/affine parameters changed after compile: "
                    "the precomputed fused-kernel affine bundles no longer "
                    "match gamma/beta/mean/var — call "
                    "snn_yolo.compile_detector(cfg, params, bn_state) again"
                )

    # -------------------------------------------------------------- calls --
    def __call__(self, frames) -> Detections:
        """frames: (N, H, W, 3) in [0, 1] -> batched Detections (cold
        membrane state — use a session for streaming video)."""
        dets, _ = self.detect(frames)
        return dets

    def detect(self, frames) -> tuple[Detections, jax.Array]:
        """Like ``__call__`` but also returns the raw head volume."""
        self.check_plan()
        head, _, dets = self._step(
            self.params, self.bn_state, jnp.asarray(frames), None
        )
        return dets, head

    def masked_step(self, frames, mem, active, cold=None):
        """One megabatched serving tick over a capacity bucket of streams.

        ``frames``: (C, H, W, 3); ``mem``: membrane pytree with C rows;
        ``active``: (C,) bool — rows where it is False are padding lanes
        whose outputs are discarded and whose membrane stays EXACTLY as it
        was (bit-identical active-row outputs regardless of what the dead
        lanes hold); ``cold``: (C,) bool — rows joining this tick, whose
        membrane is zeroed INSIDE the step (masked cold-start reset) so
        admission never touches device state eagerly. Returns ``(head,
        new_mem, detections)``. Jitted once per capacity bucket, never per
        occupancy.
        """
        self.check_plan()
        if cold is None:
            cold = jnp.zeros(jnp.shape(active), bool)
        return self._masked_step_fn(
            self.params, self.bn_state, frames, mem, active, cold
        )

    # ----------------------------------------------------------- sessions --
    def zero_state(self, batch: int):
        """Cold-start membrane pytree for a ``batch``-stream session."""
        if self.cfg.mode != "snn":
            raise ValueError(
                f"sessions stream LIF membrane state; mode={self.cfg.mode!r} "
                "has no temporal state to carry"
            )
        h, w = self.cfg.input_hw
        frames = jax.ShapeDtypeStruct((batch, h, w, 3), jnp.float32)
        _, mem_shapes, _ = jax.eval_shape(
            self._step, self.params, self.bn_state, frames, None
        )
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), mem_shapes
        )

    def new_session(self, batch: int = 1) -> "DetectorSession":
        return DetectorSession(self, batch)


class DetectorSession:
    """Streaming handle: membrane potentials persist across ``step`` calls.

    The session vectorizes ``batch`` independent streams — feed it a
    (batch, H, W, 3) frame stack per step; row i's state only ever mixes
    with row i's frames. Contract:

    * ``step(frames)`` — advance every stream by one frame; returns
      :class:`SessionStep` (postprocessed detections + raw head).
    * ``state`` — the current membrane pytree ({layer: v, ..., "head": v}).
      A fresh or just-reset session's state is all zeros, and outputs from
      it are bit-identical to the stateless ``detector(frames)`` path.
    * ``reset()`` / ``reset(i)`` — cold-start every stream / only stream i.
    """

    def __init__(self, det: CompiledDetector, batch: int = 1):
        self.det = det
        self.batch = int(batch)
        self._mem = det.zero_state(self.batch)
        self.frames_seen = 0

    @property
    def state(self):
        return self._mem

    def step(self, frames) -> SessionStep:
        frames = jnp.asarray(frames)
        if frames.ndim != 4 or frames.shape[0] != self.batch:
            raise ValueError(
                f"session batch is {self.batch}; got frames {frames.shape} "
                "(want (batch, H, W, 3))"
            )
        self.det.check_plan()
        head, self._mem, dets = self.det._step(
            self.det.params, self.det.bn_state, frames, self._mem
        )
        self.frames_seen += 1
        return SessionStep(detections=dets, head=head)

    def reset(self, index: int | None = None) -> None:
        """Zero the membrane state of every stream, or of stream ``index``."""
        if index is None:
            self._mem = jax.tree_util.tree_map(jnp.zeros_like, self._mem)
            self.frames_seen = 0
            return
        if not -self.batch <= index < self.batch:
            # JAX drops out-of-bounds scatter indices silently — a typo'd
            # stream index would "reset" nothing without this check
            raise IndexError(f"stream index {index} out of range for batch {self.batch}")
        self._mem = jax.tree_util.tree_map(
            lambda v: v.at[index].set(0.0), self._mem
        )


# ------------------------------------------------- demo / benchmark setup --


def demo_weights(cfg: sy.SNNDetConfig, *, prune_rate: float = 0.8, seed: int = 0,
                 calib_batch: int = 2):
    """Pruned + tdBN-calibrated random weights for serving demos, smoke CI
    and benchmarks (real deployments load trained checkpoints instead).
    Returns (params, bn_state, rng) — the rng continues the same stream so
    callers generate matching synthetic frames."""
    params, bn = sy.init_params(jax.random.PRNGKey(seed), cfg)
    params = pruning.prune_tree(params, prune_rate)
    rng = np.random.default_rng(seed)
    h, w = cfg.input_hw
    calib = (rng.integers(0, 256, (calib_batch, h, w, 3)) / 255.0).astype(np.float32)
    bn = sy.calibrate_bn_state(params, bn, calib, cfg)
    return params, bn, rng


def synth_streams(rng, n_streams: int, n_frames: int, hw) -> list:
    """Uint8-grid synthetic frame streams (exact under the bit-serial
    8-bit encode path): n_streams arrays of (n_frames, H, W, 3)."""
    h, w = hw
    return [
        (rng.integers(0, 256, (n_frames, h, w, 3)) / 255.0).astype(np.float32)
        for _ in range(n_streams)
    ]


# ------------------------------------------------------------ engine core --


@dataclass
class FrameRequest:
    """A video-clip detection request: F consecutive frames of one stream."""

    rid: int
    frames: Any  # (F, H, W, 3) float array in [0, 1]
    out: list = field(default_factory=list)  # per-frame Detections (numpy)
    heads: list = field(default_factory=list)  # per-frame raw head (numpy)
    done: bool = False


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class DetectorEngineCore:
    """EngineAPI backend: megabatched continuous-stream detector serving.

    Every engine tick advances ALL active streams as ONE device-resident
    megabatch:

    * Membrane/accumulator state lives on device across ticks (threaded
      through ``forward(membrane=)`` inside the compile-once handle), never
      staged through the host.
    * The pool is sized in power-of-two CAPACITY BUCKETS: the masked step
      jits once per bucket shape, so a 1000-stream workload compiles
      O(log n_slots) step functions total — never one per occupancy.
    * Join/leave remaps slot rows without recompiling OR eager device work:
      admission claims the lowest free row and marks it for a masked
      cold-start reset applied INSIDE the next jitted step; retirement just
      frees the row (the stale membrane is invisible behind the active
      mask). The only per-leaf device ops left are the rare bucket
      grow/shrink events — shrink compacts surviving rows below the new
      capacity with one gather.
    * Inactive bucket lanes are masked out of the step — their membrane is
      bit-frozen between occupants instead of evolving under blank frames —
      and a fully drained pool dispatches nothing at all.
    * Postprocess/NMS runs batched inside the same jitted step, and the
      next tick's frame upload double-buffers against this tick's compute
      (async dispatch; steady-state only, since a finishing stream remaps
      the batch layout).
    """

    def __init__(self, det: CompiledDetector, *, n_slots: int = 8,
                 min_bucket: int = 8, tracer: Optional[Tracer] = None):
        self.det = det
        # spans and counters of each tick (disabled unless given one); the
        # Engine that drives this core records into it too
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.n_slots = n_slots
        self.min_bucket = min(min_bucket, n_slots)
        h, w = det.cfg.input_hw
        self._hw = (h, w)
        # row table over the capacity bucket: _rows[row] -> engine slot or
        # None (free lane), _row_of[slot] -> row, _cursor[slot] -> next
        # frame index, _cold -> rows whose membrane must be zeroed by the
        # next step's masked cold-start reset.
        self._row_of: dict[int, int] = {}
        self._cursor: dict[int, int] = {}
        self._cold: set[int] = set()
        self.cap = self._bucket_for(0)
        self._rows: list[Optional[int]] = [None] * self.cap
        self._mem = det.zero_state(self.cap)  # device-resident across ticks
        self._staged = None  # (device frames, signature): double-buffered upload
        # host clock per tick, from its start to the head being ready:
        # read by bench/run.py (tick_host_ms)
        self.step_wall: list[float] = []

    def _bucket_for(self, n: int) -> int:
        return min(self.n_slots, max(self.min_bucket, _pow2(max(n, 1))))

    # ---------------------------------------------------------- admission --
    def validate(self, req: FrameRequest) -> Optional[str]:
        """None if ``req`` is servable, else the rejection reason — checked
        by ``Engine.submit`` (typed rejection) and again by :meth:`admit`
        BEFORE any slot/membrane state is touched."""
        frames = np.asarray(req.frames)
        h, w = self._hw
        if frames.ndim != 4 or frames.shape[0] < 1:
            return (
                f"FrameRequest.frames must be (F, H, W, 3) with F >= 1; "
                f"got {frames.shape}"
            )
        if frames.shape[1:] != (h, w, 3):
            return (
                f"FrameRequest.frames must be (F, {h}, {w}, 3) to match "
                f"the compiled detector's cfg.input_hw={self._hw}; "
                f"got {frames.shape}"
            )
        return None

    def admit(self, req: FrameRequest, slot_idx: int) -> None:
        req.frames = np.asarray(req.frames, np.float32)
        err = self.validate(req)
        if err is not None:  # reject BEFORE touching any session state
            raise ValueError(err)
        if len(self._row_of) == self.cap:  # bucket full: grow, don't re-jit
            self._grow(self._bucket_for(len(self._row_of) + 1))
        row = self._rows.index(None)  # lowest free lane
        self._rows[row] = slot_idx
        self._row_of[slot_idx] = row
        self._cursor[slot_idx] = 0
        # masked cold-start reset: the row is zeroed inside the NEXT jitted
        # step — join issues zero device ops and never recompiles
        self._cold.add(row)

    # --------------------------------------------------------- row plumbing --
    def _grow(self, new_cap: int) -> None:
        pad = new_cap - self.cap
        self._mem = jax.tree_util.tree_map(
            lambda v: jnp.concatenate(
                [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)]
            ),
            self._mem,
        )
        self._rows.extend([None] * pad)
        self.cap = new_cap

    def _shrink(self, new_cap: int) -> None:
        """Compact surviving rows below ``new_cap`` with ONE gather per
        membrane leaf, then slice the bucket. Only called on the rare
        occupancy-halved events — per-tick join/leave is pure bookkeeping."""
        perm = list(range(new_cap))
        free = [r for r in range(new_cap) if self._rows[r] is None]
        for r in range(new_cap, self.cap):
            slot = self._rows[r]
            if slot is None:
                continue
            dst = free.pop(0)
            perm[dst] = r
            self._rows[dst] = slot
            self._row_of[slot] = dst
        idx = jnp.asarray(perm)
        self._mem = jax.tree_util.tree_map(lambda v: v[idx], self._mem)
        self._rows = self._rows[:new_cap]
        self.cap = new_cap

    def _retire(self, slot: int) -> None:
        """Free ``slot``'s row. No device work: the stale membrane left in
        the lane is invisible behind the active mask, and a future occupant
        cold-starts it inside the step."""
        row = self._row_of.pop(slot)
        self._rows[row] = None
        self._cold.discard(row)
        del self._cursor[slot]

    def _occupied(self):
        return [(r, s) for r, s in enumerate(self._rows) if s is not None]

    def _signature(self, cursor_offset: int = 0):
        """Identity of one tick's frame batch: capacity + (row, slot, frame
        index) per occupied lane. The staged (double-buffered) upload is
        only used when its signature matches the tick it was staged for —
        any admission, retirement or remap misses and reassembles."""
        return (
            self.cap,
            tuple((r, s, self._cursor[s] + cursor_offset)
                  for r, s in self._occupied()),
        )

    def _assemble(self, active: dict[int, FrameRequest], offset: int = 0):
        h, w = self._hw
        batch = np.zeros((self.cap, h, w, 3), np.float32)
        for row, slot in self._occupied():
            batch[row] = active[slot].frames[self._cursor[slot] + offset]
        return batch

    # --------------------------------------------------------------- tick --
    def step(self, active: dict[int, FrameRequest]) -> list[int]:
        """Serve one tick. With an enabled tracer it records, as children of
        the engine's ``tick`` span: ``assemble`` and ``upload`` (only when
        the staged upload misses, counted in ``sync_uploads``),
        ``dispatch`` (masks, plan check and the jitted call until it
        returns), ``stage_next`` (the next tick's upload, overlapping the
        device), ``block``, ``copy_out`` and ``retire``."""
        if not self._row_of:  # fully drained pool: zero-cost skip
            return []
        tr = self.tracer
        t0 = time.perf_counter()
        occupied = self._occupied()
        tr.count("frames", len(occupied))
        sig = self._signature()
        if self._staged is not None and self._staged[1] == sig:
            frames_dev = self._staged[0]  # pre-uploaded last tick
        else:
            tr.count("sync_uploads")
            with tr.span("assemble"):
                batch = self._assemble(active)
            with tr.span("upload"):
                frames_dev = jnp.asarray(batch)
            # the asynchronous transfer keeps the host batch alive until it
            # completes; hold no other reference to it past this point
            del batch
        self._staged = None
        with tr.span("dispatch"):
            mask = np.zeros((self.cap,), bool)
            cold = np.zeros((self.cap,), bool)
            for row, _ in occupied:
                mask[row] = True
            for row in self._cold:
                cold[row] = True
            self._cold.clear()
            head, new_mem, dets = self.det.masked_step(
                frames_dev, self._mem, jnp.asarray(mask), jnp.asarray(cold)
            )
        # double-buffer: while the device chews on this tick, stage the
        # NEXT tick's upload. Steady state only — a finishing stream would
        # remap rows and invalidate the layout (the signature check above
        # would reject it anyway; skipping saves the wasted copy).
        if all(
            self._cursor[s] + 1 < len(active[s].frames) for s in self._row_of
        ):
            with tr.span("stage_next"):
                self._staged = (
                    jax.device_put(jnp.asarray(self._assemble(active, offset=1))),
                    self._signature(cursor_offset=1),
                )
        with tr.span("block"):
            jax.block_until_ready(head)
        self.step_wall.append(time.perf_counter() - t0)

        with tr.span("copy_out"):
            head_np = np.asarray(head)
            dets_np = jax.tree_util.tree_map(np.asarray, dets)  # one transfer/field
            self._mem = new_mem
            finished = []
            for row, slot in occupied:
                req = active[slot]
                req.out.append(dets_np.row(row))
                req.heads.append(head_np[row])
                self._cursor[slot] += 1
                if self._cursor[slot] >= len(req.frames):
                    finished.append(slot)
        with tr.span("retire"):
            for slot in finished:
                self._retire(slot)
            new_cap = self._bucket_for(len(self._row_of))
            if new_cap < self.cap:
                self._shrink(new_cap)
        tr.sample_memory(next(iter(head.devices())))
        return finished
