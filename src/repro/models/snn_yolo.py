"""The paper's SNN object-detection network (§II, Fig 1/2) + ANN/QNN/BNN
baselines (Table II).

Topology (inferred — Fig 1 gives the block diagram but not channel counts;
our channel plan reproduces Table I's 3.17M parameters within 0.5% and
Fig 15's operation counts within ~20%, see benchmarks/table1_ablation.py):

  encode conv 3×3   3→16   @1024×576  (ANN encoding layer, in_T=1, out_T=1)
  maxpool
  conv block  3×3  16→32   @512×288   (in_T=1, out_T=3 — mixed time steps)
  maxpool
  basic block  32→32       @256×144   (CSP, Fig 2b)
  maxpool
  basic block  32→64       @128×72
  maxpool
  basic block  64→128      @64×36
  maxpool
  basic block 128→256      @32×18
  basic block 256→256      @32×18
  output conv 1×1 256→40   @32×18     (no-reset membrane accumulation,
                                       averaged over T; YOLOv2 head:
                                       5 anchors × (5 + 3 classes))

Basic block (Fig 2b, CSPNet-style):
  shortcut: 1×1 cin→cout/2                      (tdBN + LIF)
  main:     1×1 cin→cout → 3×3 cout→cout ×2     (tdBN + LIF each)
  concat(main, shortcut) → 1×1 1.5·cout→cout    (tdBN + LIF)

LIF: threshold 0.5, leak 0.25, hard reset. All tensors NHWC; time leads:
(T, N, H, W, C).
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Any, Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import block_conv as bc
from repro.core import energy as en
from repro.core import lif as lifm
from repro.core import plan as cplan
from repro.core import pruning, quant
from repro.core import spike_conv as sc
from repro.kernels import ops as kops

Mode = Literal["snn", "ann", "qnn", "bnn"]
ConvExec = Literal["dense", "gated", "pallas"]


@dataclass(frozen=True)
class SNNDetConfig:
    arch_id: str = "snn-det"
    input_hw: tuple = (576, 1024)
    num_classes: int = 3
    num_anchors: int = 5
    stem_channels: int = 16
    conv_block_channels: int = 32
    # basic blocks: (cin, cout) pairs; pooling before each of the first 3
    stage_channels: tuple = ((32, 32), (32, 64), (64, 128), (128, 256), (256, 256))
    # how many stages have a maxpool in front (the rest run at final res)
    pooled_stages: int = 4
    full_t: int = 3
    threshold: float = 0.5
    leak: float = 0.25
    # LIF reset mode (core.lif.ResetMode): "hard" — the paper's v·(1−s)
    # (training default); "soft" — reset by subtraction, v −= θ on spike.
    # ANN→SNN conversion (repro.convert) emits "soft": with it the firing
    # rate tracks clamp(drive/θ) with O(1/T) error instead of the hard
    # reset's systematic overshoot loss, which compounds through depth.
    reset: str = "hard"
    # cold-start membrane potential of every spiking layer (streaming
    # sessions that carry v across frames override it). Conversion sets
    # θ/2: the spike count becomes round(T·y/θ) instead of floor(·) — an
    # UNBIASED rate code, killing the per-layer undercount that otherwise
    # compounds through depth.
    v_init: float = 0.0
    # pool the tdBN DRIVES (pre-LIF) instead of the spike trains at every
    # max-pool site (snn mode only). OR-ing spike trains overestimates the
    # ANN's max-pool (union rate ≥ max rate); pooling the drive commutes
    # with the monotone tdBN→LIF chain, so the converted net's pooled
    # firing rate tracks exactly the ANN's pooled activation. Training
    # keeps the paper's spike OR gate (False).
    pool_drive: bool = False
    # spike max-pool semantics (snn mode): "or" — the paper's OR gate
    # (union of the window's spike trains; its rate OVERESTIMATES the
    # ANN's max, union rate ≥ max rate); "rate" — rate-gated pooling
    # (Rueckauer et al. 2017): each window passes the CURRENT spike of
    # the input with the highest running spike count, so the pooled rate
    # tracks the max input rate. Conversion emits "rate"; training keeps
    # the paper's "or".
    pool_mode: str = "or"
    # spiking head readout: "mean" — the paper's no-reset membrane
    # averaged over T, which weights a spike at step t by (T−t+1)/T so
    # LATE spikes count less (low-rate neurons fire late under rate
    # coding and get systematically crushed); "final" — final membrane
    # divided by T, weighting every step equally (timing-free for
    # leak=1, what conversion needs).
    head_readout: str = "mean"
    mode: Mode = "snn"
    act_bits: int = 4  # QNN activation precision (Table II sweeps 2/3/4)
    weight_bits: int = 8  # 0 = float weights
    use_block_conv: bool = False
    # in_T per LIF-producing macro layer: encode, conv_block, stages...
    mixed_time: bool = True
    # rate-coded encoding: the encode layer's conv result (computed ONCE —
    # in_T stays 1) drives its LIF for full_t steps, emitting a spike TRAIN
    # instead of the paper's single binary plane. The paper's trained nets
    # learn around the 1-bit encode; ANN→SNN conversion (repro.convert)
    # cannot, so converted configs flip this on. Executor plans and the
    # fused kernel handle it unchanged (same broadcast path as conv_block).
    rate_encode: bool = False
    # which conv executor runs every layer (core/plan.py registry):
    # "dense" oracle, "gated" shift-accumulate reference, "pallas" kernel
    conv_exec: str = "dense"
    # spatial block for block conv AND the Pallas grid; every feature-map
    # resolution in the net must divide it (paper: 18×32)
    block_hw: tuple = (18, 32)
    # Pallas interpret override: None = auto-detect backend
    kernel_interpret: bool | None = None

    @property
    def head_channels(self) -> int:
        return self.num_anchors * (5 + self.num_classes)

    @property
    def grid_hw(self) -> tuple:
        # one maxpool after encode, one after conv_block, pooled_stages-1
        # between stages (the paper's 5 pools ⇒ //32 at pooled_stages=4)
        f = 2 ** (self.pooled_stages + 1)
        return (self.input_hw[0] // f, self.input_hw[1] // f)


def config_to_dict(cfg: "SNNDetConfig") -> dict:
    """JSON-serializable dict of the full config — the self-describing
    sidecar detector checkpoints carry (``harness.save_detector_checkpoint``)
    so a restore needs no out-of-band knowledge of the architecture."""
    return dataclasses.asdict(cfg)


def config_from_dict(d: dict) -> "SNNDetConfig":
    """Inverse of :func:`config_to_dict` — JSON round-trips tuples as
    lists, so the tuple-typed fields are re-tupled before construction."""
    d = dict(d)
    unknown = set(d) - {f.name for f in dataclasses.fields(SNNDetConfig)}
    if unknown:
        raise ValueError(f"unknown SNNDetConfig fields {sorted(unknown)} — "
                         "checkpoint written by an incompatible version?")
    for k in ("input_hw", "block_hw"):
        if k in d:
            d[k] = tuple(d[k])
    if "stage_channels" in d:
        d["stage_channels"] = tuple(tuple(p) for p in d["stage_channels"])
    return SNNDetConfig(**d)


# ----------------------------------------------------------------- params --


def _conv_init(key, kh, kw, cin, cout, dtype=jnp.float32):
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout), dtype) * np.sqrt(2.0 / fan_in)
    return w


def _bn_init(c):
    return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,))}


def _bn_state(c):
    return {"mean": jnp.zeros((c,)), "var": jnp.ones((c,)), "count": jnp.zeros((), jnp.int32)}


def init_params(key, cfg: SNNDetConfig):
    """Returns (params, bn_state) pytrees."""
    keys = iter(jax.random.split(key, 64))
    p: dict[str, Any] = {}
    s: dict[str, Any] = {}

    def conv_bn(name, kh, kw, cin, cout):
        p[name] = {"w": _conv_init(next(keys), kh, kw, cin, cout), **_bn_init(cout)}
        s[name] = _bn_state(cout)

    conv_bn("encode", 3, 3, 3, cfg.stem_channels)
    conv_bn("conv_block", 3, 3, cfg.stem_channels, cfg.conv_block_channels)
    for i, (cin, cout) in enumerate(cfg.stage_channels):
        half = cout // 2
        conv_bn(f"stage{i}/shortcut", 1, 1, cin, half)
        conv_bn(f"stage{i}/main_in", 1, 1, cin, cout)
        conv_bn(f"stage{i}/main_a", 3, 3, cout, cout)
        conv_bn(f"stage{i}/main_b", 3, 3, cout, cout)
        conv_bn(f"stage{i}/agg", 1, 1, cout + half, cout)
    p["head"] = {"w": _conv_init(next(keys), 1, 1, cfg.stage_channels[-1][1], cfg.head_channels)}
    return p, s


def param_count(params) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))


def calibrate_bn_state(params, bn_state, images, cfg: SNNDetConfig, *, iters: int = 25):
    """Move the tdBN running statistics onto real activation statistics by
    running train-mode forwards. Fresh stats (mean 0, var 1) silence every
    deep layer of an untrained net at eval time — serving demos, benchmarks
    and streaming-session tests calibrate first so spikes actually flow.
    Runs the dense path (no plan needed); returns the new bn_state."""
    dense_cfg = cfg if cfg.conv_exec == "dense" else dataclasses.replace(cfg, conv_exec="dense")
    step = jax.jit(lambda bn: forward(params, bn, images, dense_cfg, train=True)[1])
    for _ in range(iters):
        bn_state = step(bn_state)
    return bn_state


def default_bn_state(params):
    """Fresh inference-time bn_state (mean 0, var 1) matching ``params`` —
    what ``compile_detector`` uses when no trained statistics are given."""
    return {
        name: _bn_state(lp["w"].shape[-1])
        for name, lp in params.items()
        if "gamma" in lp
    }


# ---------------------------------------------------------------- forward --


def _conv(x, w, cfg: SNNDetConfig):
    if cfg.use_block_conv and w.shape[0] > 1:
        bh, bw = cfg.block_hw
        return bc.block_conv2d(x, w, block_h=bh, block_w=bw)
    return bc.conv2d(x, w)


def _conv_t(x_t, layer_p, cfg: SNNDetConfig, *, name=None, plan=None):
    """Run one conv layer over the (T, N, H, W, C) volume.

    With a compiled plan the layer dispatches through the pluggable
    executor registry (dense / gated / pallas — ``cfg.conv_exec``), which
    folds T into the batch; without one it falls back to the legacy
    fake-quant float path (the differentiable training path)."""
    if plan is not None and name is not None and name in plan.layers:
        return cplan.run_conv(x_t, plan.layers[name], cfg)
    w = _maybe_quant_w(layer_p["w"], cfg)
    return jax.vmap(lambda x: _conv(x, w, cfg))(x_t)


def _maybe_quant_w(w, cfg: SNNDetConfig):
    if cfg.weight_bits and cfg.mode != "bnn":
        return quant.fake_quant_tensor(w, cfg.weight_bits)
    if cfg.mode == "bnn":
        # binary weights, scaled by mean magnitude (XNOR-style)
        scale = jnp.mean(jnp.abs(w))
        return jnp.sign(w) * scale
    return w


def _tdbn(x_t, layer_p, layer_s, cfg, train):
    """x_t: (T, N, H, W, C) — tdBN pools stats over (T, N, H, W)."""
    params = lifm.TdBNParams(gamma=layer_p["gamma"], beta=layer_p["beta"])
    state = lifm.TdBNState(mean=layer_s["mean"], var=layer_s["var"], count=layer_s["count"])
    y, new_state = lifm.tdbn_apply(
        params, state, x_t, threshold=cfg.threshold, training=train
    )
    return y, {"mean": new_state.mean, "var": new_state.var, "count": new_state.count}


def _activation(y_t, cfg: SNNDetConfig, *, v0=None):
    """Post-norm nonlinearity per model family. y_t: (T, N, H, W, C).

    Returns (act, v_final). ``v0`` warm-starts the LIF membrane (streaming
    sessions carry it across frames); v_final is None for stateless modes.
    """
    if cfg.mode == "snn":
        if v0 is None and cfg.v_init:
            v0 = jnp.full(y_t.shape[1:], cfg.v_init, y_t.dtype)
        init = None if v0 is None else lifm.LIFState(v=v0)
        spikes, final = lifm.lif_over_time(
            y_t, threshold=cfg.threshold, leak=cfg.leak, reset=cfg.reset,
            init=init,
        )
        return spikes, final.v
    if cfg.mode == "ann":
        return jax.nn.relu(y_t), None
    if cfg.mode == "qnn":
        act = jax.nn.relu(y_t)
        qmax = 2**cfg.act_bits - 1
        scale = jnp.maximum(jnp.max(act), 1e-6) / qmax
        return quant.fake_quant(act, scale), None
    if cfg.mode == "bnn":
        return lifm.spike_fn(y_t, 0.0), None  # sign-ish binary activation w/ STE
    raise ValueError(cfg.mode)


def _conv_bn_act(x_t, layer_p, layer_s, cfg, train, *, name, **kw):
    """Conv (per time step) → tdBN → activation, under a named scope of the
    layer's name: every device op of the layer (the fused kernel and its
    own glue) carries the name in its op metadata, so a profiler trace
    splits device time by layer. Metadata only; the numbers are those of
    :func:`_conv_bn_act_body`."""
    with jax.named_scope(name):
        return _conv_bn_act_body(x_t, layer_p, layer_s, cfg, train, name=name, **kw)


def _fusable(x_t, t_out, layer_p, cfg, train, *, name, plan, taps) -> bool:
    """Whether a layer can run as one fused Pallas dispatch: eval mode, a
    spiking net on the pallas executor with the layer in the plan, a tdBN
    to fold, and an input of 1 or ``t_out`` steps. Recording ``taps``
    (the tdBN drive) keeps the chain unfused."""
    return (
        not train
        and taps is None
        and cfg.mode == "snn"
        and cfg.conv_exec == "pallas"
        and plan is not None
        and name in plan.layers
        and "gamma" in layer_p
        and x_t.shape[0] in (1, t_out)
    )


def _conv_bn_act_body(
    x_t, layer_p, layer_s, cfg, train, *, out_t=None, name=None, plan=None, v0=None,
    affine=None, taps=None, pool=False,
):
    """Conv (per time step) → tdBN → activation.

    Mixed time steps: if out_t > x_t.shape[0] == 1, the conv result is
    computed ONCE and broadcast to out_t steps before the LIF (paper §II-A).
    Returns (act, new_bn_state, v_final).

    ``pool``: this layer's output feeds a 2×2 max-pool. With
    ``cfg.pool_drive`` (snn mode) the pool runs HERE, on the tdBN drive
    before the LIF — the caller must then skip its own ``_maxpool_t`` —
    so the pooled firing rate tracks the ANN's pooled activation instead
    of the OR-gate union. Forces the unfused path (the fused kernel's
    conv→affine→LIF chain has no pool stage between affine and LIF).

    At eval time on the pallas executor the whole chain collapses into ONE
    fused dispatch per layer (``plan.run_fused``: conv → FXP rescale → tdBN
    affine → LIF with the membrane resident in VMEM across T) — bit-exact
    with the unfused path, so this is purely a dataflow change. When
    ``taps`` is given the chain stays unfused so the tdBN output can be
    recorded — numerics are identical either way (PR 6 conformance).
    The 8-bit encoding layer's fused form is :func:`_encode_pool`'s.
    """
    t_out = out_t or x_t.shape[0]
    pool_inside = pool and cfg.pool_drive and cfg.mode == "snn"
    if (
        not pool_inside
        and _fusable(x_t, t_out, layer_p, cfg, train, name=name, plan=plan,
                     taps=taps)
        and plan.layers[name].in_bits == 1
    ):
        act, v_final = cplan.run_fused(
            x_t,
            plan.layers[name],
            cfg,
            gamma=layer_p["gamma"],
            beta=layer_p["beta"],
            mean=layer_s["mean"],
            var=layer_s["var"],
            v0=v0,
            out_t=t_out,
            affine=affine,
        )
        return act, layer_s, v_final  # eval-mode tdBN state is unchanged
    y_t = _conv_t(x_t, layer_p, cfg, name=name, plan=plan)
    if out_t is not None and out_t != y_t.shape[0]:
        assert y_t.shape[0] == 1, "can only broadcast from T=1"
        y_t = jnp.broadcast_to(y_t, (out_t,) + y_t.shape[1:])
    y_t, new_s = _tdbn(y_t, layer_p, layer_s, cfg, train)
    if taps is not None and name is not None:
        taps[name] = y_t  # tdBN output, PRE-pool (matches the ANN taps)
    if pool_inside:
        y_t = _maxpool_t(y_t)
    act, v_final = _activation(y_t, cfg, v0=v0)
    return act, new_s, v_final


def _encode_pool(x_t, layer_p, layer_s, cfg, train, *, out_t, plan, v0, affine,
                 taps):
    """The 8-bit encoding layer and the 2×2 pool after it (``pool0``).
    Returns (pooled spikes, spikes, new bn state, membrane).

    On the pallas executor at eval time the layer is one lane-dense
    dispatch (``plan.run_encode``): its spikes and membrane keep the
    lane-dense shape (N, H, W·C) of NHWC element order, the pool
    reads the int8 spikes in that shape, and the membrane leaf a session
    carries stays in it from frame to frame. The returned spikes have the
    NHWC shape. Everywhere else the layer is :func:`_conv_bn_act`, then
    :func:`_pool_t` (with ``cfg.pool_drive`` the pool ran inside)."""
    t_out = out_t or 1
    pool_drive = cfg.pool_drive and cfg.mode == "snn"
    bh, bw = cfg.block_hw
    _, _, h, w, _ = x_t.shape
    if (
        not pool_drive
        and _fusable(x_t, t_out, layer_p, cfg, train, name="encode",
                     plan=plan, taps=taps)
        and h % bh == 0 and w % bw == 0  # whole blocks, as block conv needs
    ):
        c = layer_p["w"].shape[-1]
        with jax.named_scope("encode"):
            spk, v = cplan.run_encode(
                x_t, plan.layers["encode"], cfg, gamma=layer_p["gamma"],
                beta=layer_p["beta"], mean=layer_s["mean"],
                var=layer_s["var"], v0=v0, out_t=t_out, affine=affine,
            )
            s_t = kops.lane_dense_nhwc(spk, c).astype(jnp.float32)
        with jax.named_scope("pool0"):
            if cfg.pool_mode == "or" and h % 2 == 0 and w % 2 == 0:
                pooled = kops.lane_dense_maxpool(spk, c).astype(jnp.float32)
            else:
                pooled = _pool_t(s_t, cfg)
        return pooled, s_t, layer_s, v  # eval-mode tdBN state is unchanged
    s_t, new_s, v = _conv_bn_act(
        x_t, layer_p, layer_s, cfg, train, out_t=out_t, name="encode",
        plan=plan, v0=v0, affine=affine, taps=taps, pool=True,
    )
    pooled = s_t
    if not pool_drive:
        with jax.named_scope("pool0"):
            pooled = _pool_t(s_t, cfg)
    return pooled, s_t, new_s, v


def _maxpool_t(x_t):
    """2×2 spike max-pool == OR gate (paper's max-pooling module)."""
    return jax.vmap(
        lambda x: jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
    )(x_t)


def _rate_gated_pool_t(s_t):
    """2×2 rate-gated spike pool (Rueckauer et al. 2017): each window
    emits the CURRENT spike of the input with the highest cumulative
    spike count, so the pooled rate converges to the max input rate —
    the OR gate's union rate systematically overestimates it. Counts are
    encoded into the max-reduce key as 2·count + spike (count ≤ T ≪ 2²³
    so the f32 encoding is exact); ties break toward a spiking input,
    which makes the first steps degrade gracefully to the OR gate."""

    def step(c, s):
        c = c + s
        key = c * 2.0 + s
        m = jax.lax.reduce_window(
            key, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
        return c, m % 2.0

    _, out = jax.lax.scan(step, jnp.zeros_like(s_t[0]), s_t)
    return out


def _pool_t(s_t, cfg: SNNDetConfig):
    """Pool a spike/activation volume per ``cfg.pool_mode`` (snn mode
    only — ann/qnn/bnn activations are real-valued, where max IS max)."""
    if cfg.mode == "snn" and cfg.pool_mode == "rate":
        return _rate_gated_pool_t(s_t)
    return _maxpool_t(s_t)


def forward(
    params,
    bn_state,
    images,
    cfg: SNNDetConfig,
    *,
    train: bool = False,
    plan=None,
    membrane=None,
    affines=None,
    taps=None,
):
    """images: (N, H, W, 3) in [0, 1]. Returns (head, new_bn_state, aux).

    head: (N, gh, gw, anchors, 5 + classes) raw predictions.
    aux["spikes"]: per-macro-layer spike tensors for mIoUT analysis.
    aux["membrane"]: final LIF membrane potential per layer (plus the head
    accumulator under "head") — the streaming state a
    :class:`repro.serve.detector.DetectorSession` threads across frames.

    ``plan``: a precompiled :class:`repro.core.plan.DetectorPlan`. Required
    for ``cfg.conv_exec`` other than "dense" — every conv layer then runs
    through the compressed executor. Plan ownership (build, cache, staleness
    checks) lives in :func:`compile_detector`; this free function is the
    internal core the handle wraps.

    ``membrane``: optional {layer_name: v} dict warm-starting every LIF
    membrane (cold start when None or when a layer key is missing).

    ``affines``: optional {layer_name: bundle} of precomputed fused-kernel
    affine parameter bundles (:func:`repro.core.plan.precompute_affines`) —
    compile-once callers hoist the per-layer bundle build out of the frame
    loop; missing keys fall back to the inline build (same values).

    ``taps``: optional mutable dict — when given, every layer records its
    tdBN output (the per-step LIF input drive, shape (T, N, H, W, C)) under
    its layer name, plus the raw head conv output under "head". Used by the
    ANN→SNN conversion front-end (:mod:`repro.convert`) to verify rescale
    exactness and fit the head readout scale; forces the unfused path.
    """
    if cfg.conv_exec != "dense" and cfg.mode != "snn":
        # compressed executors consume int8 binary spikes; ann/qnn/bnn
        # activations are multibit floats and would truncate silently
        raise ValueError(
            f"conv_exec={cfg.conv_exec!r} requires mode='snn' (got "
            f"mode={cfg.mode!r}: activations are not binary spikes)"
        )
    if cfg.conv_exec != "dense" and not cfg.weight_bits:
        raise ValueError(
            f"conv_exec={cfg.conv_exec!r} requires weight_bits > 0 (the "
            "compressed plan is FXP int8; weight_bits=0 means float weights)"
        )
    if plan is not None and tuple(plan.block_hw) != tuple(cfg.block_hw):
        raise ValueError(
            f"plan was built for block_hw={tuple(plan.block_hw)} but "
            f"cfg.block_hw={tuple(cfg.block_hw)}; rebuild the plan"
        )
    if plan is None and cfg.conv_exec != "dense":
        raise ValueError(
            f"conv_exec={cfg.conv_exec!r} needs a precompiled plan: use "
            "repro.models.snn_yolo.compile_detector(cfg, params) (which owns "
            "plan build/cache/staleness), or call "
            "repro.core.plan.build_plan(params, cfg) outside jit and pass it "
            "as forward(..., plan=plan)"
        )
    full_t = 1 if cfg.mode != "snn" else cfg.full_t
    new_state = dict(bn_state)
    aff = affines or {}
    mem = membrane or {}
    new_mem: dict[str, Any] = {}
    aux: dict[str, Any] = {"spikes": {}, "membrane": new_mem}

    x = images.astype(jnp.float32)
    x_t = x[None]  # encoding layer sees the raw image once (in_T = 1)

    # --- encode (ANN layer: fires once — or rate-codes when rate_encode) ---
    enc_t = full_t if (cfg.rate_encode and cfg.mode == "snn") else None
    pd = cfg.pool_drive and cfg.mode == "snn"  # pools already ran inside
    s_t, aux["spikes"]["encode"], new_state["encode"], new_mem["encode"] = (
        _encode_pool(
            x_t, params["encode"], bn_state["encode"], cfg, train, out_t=enc_t,
            plan=plan, v0=mem.get("encode"), affine=aff.get("encode"),
            taps=taps,
        )
    )
    n_pool = 0 if pd else 1

    # --- conv block: in_T=1, out_T=full_t (mixed time steps) ---
    out_t = full_t if cfg.mixed_time else s_t.shape[0]
    if not cfg.mixed_time and cfg.mode == "snn":
        # non-mixed baseline: replicate the input spikes to full_t steps
        s_t = jnp.broadcast_to(s_t, (full_t,) + s_t.shape[1:])
        out_t = full_t
    s_t, new_state["conv_block"], new_mem["conv_block"] = _conv_bn_act(
        s_t, params["conv_block"], bn_state["conv_block"], cfg, train, out_t=out_t,
        name="conv_block", plan=plan, v0=mem.get("conv_block"),
        affine=aff.get("conv_block"), taps=taps, pool=True,
    )
    aux["spikes"]["conv_block"] = s_t
    if not pd:
        with jax.named_scope(f"pool{n_pool}"):
            s_t = _pool_t(s_t, cfg)
        n_pool += 1

    # --- CSP basic blocks ---
    for i in range(len(cfg.stage_channels)):
        name = f"stage{i}"

        def cba(x_in, lname, pool=False):
            return _conv_bn_act(
                x_in, params[lname], bn_state[lname], cfg, train, name=lname,
                plan=plan, v0=mem.get(lname), affine=aff.get(lname), taps=taps,
                pool=pool,
            )

        short, new_state[f"{name}/shortcut"], new_mem[f"{name}/shortcut"] = cba(
            s_t, f"{name}/shortcut"
        )
        m, new_state[f"{name}/main_in"], new_mem[f"{name}/main_in"] = cba(
            s_t, f"{name}/main_in"
        )
        m, new_state[f"{name}/main_a"], new_mem[f"{name}/main_a"] = cba(m, f"{name}/main_a")
        m, new_state[f"{name}/main_b"], new_mem[f"{name}/main_b"] = cba(m, f"{name}/main_b")
        with jax.named_scope(f"{name}/concat"):
            cat = jnp.concatenate([m, short], axis=-1)
        s_t, new_state[f"{name}/agg"], new_mem[f"{name}/agg"] = cba(
            cat, f"{name}/agg", pool=i < cfg.pooled_stages - 1
        )
        aux["spikes"][name] = s_t
        if i < cfg.pooled_stages - 1 and not pd:
            with jax.named_scope(f"pool{n_pool}"):
                s_t = _pool_t(s_t, cfg)
            n_pool += 1

    # --- output conv: accumulate membrane with no reset, average over T ---
    with jax.named_scope("head"):
        y_t = _conv_t(s_t, params["head"], cfg, name="head", plan=plan)
        if taps is not None:
            taps["head"] = y_t
        if cfg.mode == "snn":
            head, new_mem["head"] = lifm.membrane_readout(
                y_t, leak=cfg.leak, v0=mem.get("head"), return_final=True
            )
            if cfg.head_readout == "final":
                # final membrane / T: every step weighted equally (the mean
                # readout weights step t by (T−t+1)/T, biased against the
                # late first-spikes of low-rate neurons)
                head = new_mem["head"] / y_t.shape[0]
        else:
            head = jnp.mean(y_t, axis=0)
        n, gh, gw, _ = head.shape
        head = head.reshape(n, gh, gw, cfg.num_anchors, 5 + cfg.num_classes)
    return head, new_state, aux


# ------------------------------------------------------- layer accounting --


# Per-layer post-pruning densities of the 3×3 kernels, shaped like paper
# Fig 3: a single global magnitude threshold keeps far more weights in the
# small early layers than in the large late ones. Calibrated so the model
# reproduces BOTH Table I (−70% params) and §IV-E (−47.3% ops) jointly.
FIG3_DENSITY_PROFILE = {
    "encode": 0.70,
    "conv_block": 0.70,
    "stage0": 0.70,
    "stage1": 0.50,
    "stage2": 0.50,
    "stage3": 0.12,
    "stage4": 0.12,
}


def layer_specs(
    cfg: SNNDetConfig, *, pruned_density: float | dict | None = None
) -> list[en.ConvLayerSpec]:
    """The network as a ConvLayerSpec list for the §IV-D/E energy model.

    density applies to 3×3 kernels only (paper prunes only those at 80%).
    ``pruned_density``: None → Fig 3 profile; float → uniform; dict →
    per-group override. Time steps follow the (1, full_t) mixed schedule.
    """
    H, W = cfg.input_hw
    t = cfg.full_t
    specs: list[en.ConvLayerSpec] = []
    if pruned_density is None:
        profile = FIG3_DENSITY_PROFILE
    elif isinstance(pruned_density, dict):
        profile = pruned_density
    else:
        profile = {k: pruned_density for k in FIG3_DENSITY_PROFILE}

    specs.append(
        en.ConvLayerSpec(
            "encode", H, W, 3, cfg.stem_channels, 3, 1, 1, bits_in=8, density=profile["encode"]
        )
    )
    h, w = H // 2, W // 2
    specs.append(
        en.ConvLayerSpec(
            "conv_block",
            h,
            w,
            cfg.stem_channels,
            cfg.conv_block_channels,
            3,
            1,
            t,
            density=profile["conv_block"],
        )
    )
    h, w = h // 2, w // 2
    for i, (cin, cout) in enumerate(cfg.stage_channels):
        half = cout // 2
        d3 = profile[f"stage{i}"]
        specs += [
            en.ConvLayerSpec(f"stage{i}/shortcut", h, w, cin, half, 1, t, t),
            en.ConvLayerSpec(f"stage{i}/main_in", h, w, cin, cout, 1, t, t),
            en.ConvLayerSpec(f"stage{i}/main_a", h, w, cout, cout, 3, t, t, density=d3),
            en.ConvLayerSpec(f"stage{i}/main_b", h, w, cout, cout, 3, t, t, density=d3),
            en.ConvLayerSpec(f"stage{i}/agg", h, w, cout + half, cout, 1, t, t),
        ]
        if i < cfg.pooled_stages - 1:
            h, w = h // 2, w // 2
    gh, gw = cfg.grid_hw
    specs.append(
        en.ConvLayerSpec(
            "head", gh, gw, cfg.stage_channels[-1][1], cfg.head_channels, 1, t, t, bits_out=8
        )
    )
    return specs


# ------------------------------------------------------------- YOLOv2 head -


def decode_head(head, anchors, *, threshold=None):
    """YOLOv2 box decode. head: (N, gh, gw, A, 5+C) raw.
    Returns (boxes_xywh [0-1 normalized], obj, class_probs).

    This is the EXACT inverse of the training-target encoding
    (``data/synthetic_detection.sample``: best-shape-IoU anchor, tx/ty as
    within-cell offsets, tw/th log-scale vs that anchor) — a head that
    fits its targets decodes to the ground-truth boxes, which is what
    makes ``repro.eval.detection_map`` mAP meaningful
    (tests/test_eval_map.py pins the round trip at mAP 1.0).

    ``threshold``: score threshold on the objectness — boxes whose obj
    score falls below it get obj zeroed, so downstream stages (NMS, the
    serve postprocess) can treat obj > 0 as the validity mask. Box
    coordinates and class probabilities are left intact.
    """
    txy = jax.nn.sigmoid(head[..., 0:2])
    twh = head[..., 2:4]
    obj = jax.nn.sigmoid(head[..., 4])
    if threshold is not None:
        obj = jnp.where(obj >= threshold, obj, 0.0)
    cls = jax.nn.softmax(head[..., 5:], axis=-1)
    n, gh, gw, a, _ = head.shape
    gy, gx = jnp.meshgrid(jnp.arange(gh), jnp.arange(gw), indexing="ij")
    cx = (gx[None, :, :, None] + txy[..., 0]) / gw
    cy = (gy[None, :, :, None] + txy[..., 1]) / gh
    anchors = jnp.asarray(anchors)  # (A, 2) in grid-cell units
    bw = anchors[:, 0] * jnp.exp(twh[..., 0]) / gw
    bh = anchors[:, 1] * jnp.exp(twh[..., 1]) / gh
    boxes = jnp.stack([cx, cy, bw, bh], axis=-1)
    return boxes, obj, cls


DEFAULT_ANCHORS = ((1.0, 1.0), (2.0, 2.0), (4.0, 2.5), (2.5, 4.0), (6.0, 6.0))


def compile_detector(cfg: SNNDetConfig, params, bn_state=None, **kwargs):
    """Compile-once entry point: returns a
    :class:`repro.serve.detector.CompiledDetector` owning the
    :class:`~repro.core.plan.DetectorPlan`, the jitted executor-backed
    forward, and the postprocess stage (decode → score threshold → NMS)::

        det = compile_detector(cfg, params)
        dets = det(frames)                    # Detections, zero plan plumbing
        sess = det.new_session()              # streaming membrane state

    See :mod:`repro.serve.detector` for the full handle/session API;
    ``**kwargs`` (anchors, score/iou thresholds, prune_rate, ...) forward to
    the ``CompiledDetector`` constructor.
    """
    from repro.serve.detector import CompiledDetector  # circular-import guard

    return CompiledDetector(cfg, params, bn_state, **kwargs)


def yolo_loss(head, targets, anchors=DEFAULT_ANCHORS, *, l_coord=5.0, l_noobj=0.5):
    """YOLOv2-style loss. targets: (N, gh, gw, A, 5+C) with
    [tx, ty, tw, th, obj, onehot-classes]; obj∈{0,1} marks assigned anchors.
    tx/ty are within-cell offsets in (0,1); tw/th are log-scale vs the
    assigned anchor — the ``decode_head`` inverse domain, so minimizing
    this loss directly maximizes decoded-box IoU (see decode_head)."""
    obj_mask = targets[..., 4]
    noobj_mask = 1.0 - obj_mask
    pxy = jax.nn.sigmoid(head[..., 0:2])
    pwh = head[..., 2:4]
    pobj = jax.nn.sigmoid(head[..., 4])
    plog = jax.nn.log_softmax(head[..., 5:], axis=-1)

    coord = jnp.sum(obj_mask[..., None] * ((pxy - targets[..., 0:2]) ** 2 + (pwh - targets[..., 2:4]) ** 2))
    obj_l = jnp.sum(obj_mask * (pobj - 1.0) ** 2)
    noobj_l = jnp.sum(noobj_mask * pobj**2)
    cls_l = -jnp.sum(obj_mask[..., None] * targets[..., 5:] * plog)
    n = head.shape[0]
    return (l_coord * coord + obj_l + l_noobj * noobj_l + cls_l) / n
