"""Plain reference of the spiking detector, and the weights both sides use.

Written from the paper's description (arXiv 2205.00778, §II) in
straightforward ``jax.numpy``, float32, at ``Precision.HIGHEST``, with no
kernel, plan, cache or batching of the program: it imports nothing of the
program. Per layer and frame:

* FXP weights: per-tensor symmetric, ``q = clip(round(w / s), -2^(b-1),
  2^(b-1) - 1)``, ``s = max|w| / (2^(b-1) - 1)``;
* block convolution: the map is cut into ``block_hw`` blocks, each padded by
  replicating its own border, then convolved (3×3 or 1×1) on the integer
  weights; the sum is scaled once by ``s`` (by ``s / 255`` for the 8-bit
  encode layer, whose input is the u8 pixel value);
* tdBN at inference: ``((y - mean) * rsqrt(var + eps)) * threshold * gamma
  + beta``;
* LIF: ``v = v * leak + drive``, spike where ``v >= threshold``, hard reset;
* mixed time steps: the encode layer fires once; ``conv_block`` convolves
  one input step and drives its LIF for ``full_t`` steps (with
  ``mixed_time`` false it convolves ``full_t`` copies of its input);
* 2×2 max-pool of spikes (an OR gate), CSP blocks, and the 1×1 head whose
  membrane accumulates with no reset and is averaged over the steps;
* YOLOv2 decode, score threshold and greedy class-aware NMS.

Membranes carry from frame to frame within a clip and start at zero.

The benchmark also makes the weights here, from the configuration's weight
seed: He-normal kernels with the 3×3 ones pruned by magnitude, unit tdBN
gains, and tdBN statistics measured by this reference on calibration frames.
The program receives the float kernels and statistics and quantizes them
itself; the reference quantizes its own copy.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5


class Net(NamedTuple):
    """The sizes of the network, read from a configuration file's
    ``model`` group."""

    input_hw: tuple
    num_classes: int
    num_anchors: int
    stem_channels: int
    conv_block_channels: int
    stage_channels: tuple
    pooled_stages: int
    full_t: int
    threshold: float
    leak: float
    mixed_time: bool
    block_hw: tuple

    @staticmethod
    def from_model(m: dict) -> "Net":
        return Net(
            input_hw=tuple(m["input_hw"]), num_classes=m["num_classes"],
            num_anchors=m["num_anchors"], stem_channels=m["stem_channels"],
            conv_block_channels=m["conv_block_channels"],
            stage_channels=tuple(tuple(p) for p in m["stage_channels"]),
            pooled_stages=m["pooled_stages"], full_t=m["full_t"],
            threshold=m["threshold"], leak=m["leak"],
            mixed_time=m["mixed_time"], block_hw=tuple(m["block_hw"]),
        )

    @property
    def head_channels(self) -> int:
        return self.num_anchors * (5 + self.num_classes)

    @property
    def grid_hw(self) -> tuple:
        f = 2 ** (self.pooled_stages + 1)
        return self.input_hw[0] // f, self.input_hw[1] // f


class Layer(NamedTuple):
    """One conv layer of the network as the work counter and the reference
    see it. ``t_in``: input time steps the conv is evaluated at;
    ``t_out``: LIF steps (the head's readout steps)."""

    name: str
    k: int
    cin: int
    cout: int
    h: int
    w: int
    t_in: int
    t_out: int
    in_bits: int


def layers(net: Net) -> list[Layer]:
    """Every conv layer in network order, the head last."""
    h, w = net.input_hw
    t = net.full_t
    out = [Layer("encode", 3, 3, net.stem_channels, h, w, 1, 1, 8)]
    h, w = h // 2, w // 2
    out.append(Layer("conv_block", 3, net.stem_channels, net.conv_block_channels,
                     h, w, 1 if net.mixed_time else t, t, 1))
    h, w = h // 2, w // 2
    for i, (cin, cout) in enumerate(net.stage_channels):
        half = cout // 2
        out += [
            Layer(f"stage{i}/shortcut", 1, cin, half, h, w, t, t, 1),
            Layer(f"stage{i}/main_in", 1, cin, cout, h, w, t, t, 1),
            Layer(f"stage{i}/main_a", 3, cout, cout, h, w, t, t, 1),
            Layer(f"stage{i}/main_b", 3, cout, cout, h, w, t, t, 1),
            Layer(f"stage{i}/agg", 1, cout + half, cout, h, w, t, t, 1),
        ]
        if i < net.pooled_stages - 1:
            h, w = h // 2, w // 2
    gh, gw = net.grid_hw
    out.append(Layer("head", 1, net.stage_channels[-1][1], net.head_channels,
                     gh, gw, t, t, 1))
    return out


# ------------------------------------------------------------------ weights --


def make_params(net: Net, seed: int, prune_rate: float) -> dict:
    """Float weights from ``seed`` in one jitted call on the device:
    He-normal kernels (HWIO), the 3×3 ones pruned by magnitude at
    ``prune_rate``; tdBN gamma 1 and beta 0. The structure is the one the
    program's ``compile_detector`` takes."""
    specs = layers(net)

    def build(key):
        keys = jax.random.split(key, len(specs))
        params = {}
        for key_l, lay in zip(keys, specs):
            shape = (lay.k, lay.k, lay.cin, lay.cout)
            wt = jax.random.normal(key_l, shape, jnp.float32) * np.sqrt(
                2.0 / (lay.k * lay.k * lay.cin))
            if lay.k > 1:
                flat = jnp.sort(jnp.abs(wt).reshape(-1))
                cut = flat[int(np.floor(prune_rate * flat.size)) - 1]
                wt = jnp.where(jnp.abs(wt) > cut, wt, 0.0)
            params[lay.name] = {"w": wt}
            if lay.name != "head":
                params[lay.name]["gamma"] = jnp.ones((lay.cout,), jnp.float32)
                params[lay.name]["beta"] = jnp.zeros((lay.cout,), jnp.float32)
        return params

    return jax.jit(build)(jax.random.PRNGKey(seed % 2**32))


def quantize(w, bits: int):
    """Per-tensor symmetric FXP: (integer values as float32, scale)."""
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(w))
    scale = jnp.where(amax > 0, amax, float(qmax)) / qmax
    q = jnp.clip(jnp.round(w / scale), -qmax - 1, qmax)
    return q, scale.astype(jnp.float32)


def prepare(net: Net, params: dict, bn: dict | None, bits: int) -> dict:
    """The reference's own weights: every kernel quantized to ``bits``, with
    its scale (divided by 255 for the u8 encode input) and, where ``bn`` is
    given, the layer's tdBN constants."""
    out = {}
    for lay in layers(net):
        q, scale = quantize(params[lay.name]["w"], bits)
        entry = {"q": q, "scale": scale / 255.0 if lay.in_bits == 8 else scale}
        if lay.name != "head":
            entry["gamma"] = params[lay.name]["gamma"]
            entry["beta"] = params[lay.name]["beta"]
            if bn is not None:
                entry["mean"] = bn[lay.name]["mean"]
                entry["var"] = bn[lay.name]["var"]
        out[lay.name] = entry
    return out


# ------------------------------------------------------------------ forward --


def block_conv(x, q, block_hw):
    """(M, H, W, C) × (k, k, C, K) integer-valued f32 → (M, H, W, K): each
    block convolved on its own with its border replicated."""
    k = q.shape[0]
    if k == 1:
        return jnp.einsum("mhwc,ck->mhwk", x, q[0, 0], precision=HIGHEST)
    bh, bw = block_hw
    m, h, w, c = x.shape
    xb = x.reshape(m, h // bh, bh, w // bw, bw, c).transpose(0, 1, 3, 2, 4, 5)
    xb = xb.reshape(-1, bh, bw, c)
    p = (k - 1) // 2
    xb = jnp.pad(xb, ((0, 0), (p, p), (p, p), (0, 0)), mode="edge")
    y = jax.lax.conv_general_dilated(
        xb, q, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)
    y = y.reshape(m, h // bh, w // bw, bh, bw, -1).transpose(0, 1, 3, 2, 4, 5)
    return y.reshape(m, h, w, -1)


def _conv_t(x_t, wl, block_hw):
    t, n = x_t.shape[:2]
    y = block_conv(x_t.reshape((t * n,) + x_t.shape[2:]), wl["q"], block_hw)
    return (y * wl["scale"]).reshape((t, n) + y.shape[1:])


def _spiking(x_t, wl, v0, net: Net, t_out: int, stats: dict | None, name: str):
    """Conv → tdBN → LIF over ``t_out`` steps. With ``stats`` given, the
    layer's tdBN mean and variance are measured on this batch instead
    (calibration) and recorded there."""
    y = _conv_t(x_t, wl, net.block_hw)
    if stats is not None:
        mean = jnp.mean(y, axis=(0, 1, 2, 3))
        var = jnp.var(y, axis=(0, 1, 2, 3))
        stats[name] = {"mean": mean, "var": var}
    else:
        mean, var = wl["mean"], wl["var"]
    x_hat = (y - mean) * jax.lax.rsqrt(var + EPS)
    drive = (net.threshold * x_hat) * wl["gamma"] + wl["beta"]
    v = jnp.zeros(drive.shape[1:], jnp.float32) if v0 is None else v0
    spikes = []
    for t in range(t_out):
        v = v * net.leak + drive[0 if drive.shape[0] == 1 else t]
        s = v >= net.threshold
        spikes.append(s.astype(jnp.float32))
        v = jnp.where(s, 0.0, v)
    return jnp.stack(spikes), v


def _pool(s_t):
    return jax.lax.reduce_window(s_t, -jnp.inf, jax.lax.max,
                                 (1, 1, 2, 2, 1), (1, 1, 2, 2, 1), "VALID")


def forward(w: dict, frames, mem: dict | None, net: Net, stats: dict | None = None):
    """One frame for each of N streams. frames: (N, H, W, 3) in [0, 1] on
    the u8 grid; mem: per-layer membranes (None: all zero). Returns
    (head (N, gh, gw, A, 5+C), new membranes)."""
    mem = mem or {}
    new = {}

    def lif(x_t, name, t_out):
        s, new[name] = _spiking(x_t, w[name], mem.get(name), net, t_out, stats, name)
        return s

    u8 = jnp.clip(jnp.round(frames * 255.0), 0, 255)
    s = _pool(lif(u8[None], "encode", 1))
    if not net.mixed_time:
        s = jnp.broadcast_to(s, (net.full_t,) + s.shape[1:])
    s = _pool(lif(s, "conv_block", net.full_t))
    t = net.full_t
    for i in range(len(net.stage_channels)):
        pre = f"stage{i}/"
        short = lif(s, pre + "shortcut", t)
        m = lif(s, pre + "main_in", t)
        m = lif(m, pre + "main_a", t)
        m = lif(m, pre + "main_b", t)
        s = lif(jnp.concatenate([m, short], axis=-1), pre + "agg", t)
        if i < net.pooled_stages - 1:
            s = _pool(s)
    y = _conv_t(s, w["head"], net.block_hw)
    v = jnp.zeros(y.shape[1:], jnp.float32) if mem.get("head") is None else mem["head"]
    vs = []
    for step in range(y.shape[0]):
        v = v * net.leak + y[step]
        vs.append(v)
    new["head"] = v
    head = jnp.mean(jnp.stack(vs), axis=0)
    n, gh, gw, _ = head.shape
    return head.reshape(n, gh, gw, net.num_anchors, 5 + net.num_classes), new


def calibrate(net: Net, params: dict, frames, bits: int) -> dict:
    """tdBN statistics: each layer's conv output mean and variance over
    (steps, frames, rows, columns) on ``frames``, layer after layer."""

    def run(params, frames):
        stats: dict = {}
        forward(prepare(net, params, None, bits), frames, None, net, stats=stats)
        return stats

    stats = jax.jit(run)(params, frames)
    return {name: {"mean": st["mean"], "var": st["var"],
                   "count": jnp.zeros((), jnp.int32)} for name, st in stats.items()}


# -------------------------------------------------------------- postprocess --


def _iou(a, b):
    ax0, ay0 = a[..., 0] - a[..., 2] / 2, a[..., 1] - a[..., 3] / 2
    ax1, ay1 = a[..., 0] + a[..., 2] / 2, a[..., 1] + a[..., 3] / 2
    bx0, by0 = b[..., 0] - b[..., 2] / 2, b[..., 1] - b[..., 3] / 2
    bx1, by1 = b[..., 0] + b[..., 2] / 2, b[..., 1] + b[..., 3] / 2
    iw = jnp.maximum(jnp.minimum(ax1, bx1) - jnp.maximum(ax0, bx0), 0.0)
    ih = jnp.maximum(jnp.minimum(ay1, by1) - jnp.maximum(ay0, by0), 0.0)
    inter = iw * ih
    union = a[..., 2] * a[..., 3] + b[..., 2] * b[..., 3] - inter
    return inter / jnp.maximum(union, 1e-9)


def detect(head, anchors, score_threshold: float, iou_threshold: float,
           max_detections: int):
    """YOLOv2 decode → score threshold → greedy class-aware NMS, per frame.
    Returns (boxes (N, D, 4) cx/cy/w/h, scores (N, D), classes (N, D),
    valid (N, D)); rows past the picks are zero."""
    n, gh, gw, a, _ = head.shape
    txy = jax.nn.sigmoid(head[..., 0:2])
    obj = jax.nn.sigmoid(head[..., 4])
    obj = jnp.where(obj >= score_threshold, obj, 0.0)
    cls = jax.nn.softmax(head[..., 5:], axis=-1)
    gy, gx = jnp.meshgrid(jnp.arange(gh), jnp.arange(gw), indexing="ij")
    anchors = jnp.asarray(anchors, jnp.float32)
    boxes = jnp.stack([
        (gx[None, :, :, None] + txy[..., 0]) / gw,
        (gy[None, :, :, None] + txy[..., 1]) / gh,
        anchors[:, 0] * jnp.exp(head[..., 2]) / gw,
        anchors[:, 1] * jnp.exp(head[..., 3]) / gh,
    ], axis=-1).reshape(n, -1, 4)
    score = obj * jnp.max(cls, axis=-1)
    score = jnp.where(score >= score_threshold, score, 0.0).reshape(n, -1)
    label = jnp.argmax(cls, axis=-1).astype(jnp.int32).reshape(n, -1)

    def one(b, s, c):
        live = jnp.where(s > 0.0, s, -jnp.inf)
        picks, oks = [], []
        for _ in range(min(max_detections, b.shape[0])):
            i = jnp.argmax(live)
            ok = live[i] > 0.0
            gone = ok & (c == c[i]) & (_iou(b, b[i]) >= iou_threshold)
            live = jnp.where(gone, -jnp.inf, live)
            picks.append(i)
            oks.append(ok)
        idx = jnp.stack(picks)
        ok = jnp.stack(oks)
        okf = ok.astype(jnp.float32)
        return b[idx] * okf[:, None], s[idx] * okf, c[idx] * ok.astype(jnp.int32), ok

    return jax.vmap(one)(boxes, score, label)


@functools.partial(jax.jit, static_argnames=("net", "post"))
def step(w: dict, frames, mem: dict | None, *, net: Net, post: tuple):
    """One reference frame step with its detections. ``post``: (anchors,
    score threshold, IoU threshold, max detections), hashable."""
    head, mem = forward(w, frames, mem, net)
    anchors, score_t, iou_t, max_d = post
    return head, mem, detect(head, anchors, score_t, iou_t, max_d)


# ---------------------------------------------------------------- comparison --


def replay(w: dict, clips: list, net: Net, post: tuple, batch: int) -> list:
    """Run the reference over whole clips from a cold membrane, ``batch``
    clips of one length at a time (a short group is padded with copies of
    its first clip, so one compiled step serves every group). Returns, per
    clip, a list of per-frame (head, (boxes, scores, classes, valid)) as
    numpy arrays."""
    h, wd = net.input_hw
    frames_shape = jax.ShapeDtypeStruct((batch, h, wd, 3), jnp.float32)
    mem_shape = jax.eval_shape(lambda fr: forward(w, fr, None, net)[1], frames_shape)
    out: list = [None] * len(clips)
    by_len: dict = {}
    for i, clip in enumerate(clips):
        by_len.setdefault(len(clip), []).append(i)
    for n_frames, idx in sorted(by_len.items()):
        for g in range(0, len(idx), batch):
            group = idx[g:g + batch]
            rows = group + [group[0]] * (batch - len(group))
            mem = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), mem_shape)
            for i in group:
                out[i] = []
            for f in range(n_frames):
                frames = jnp.asarray(np.stack([clips[i][f] for i in rows]))
                head, mem, dets = step(w, frames, mem, net=net, post=post)
                head = np.asarray(head)
                dets = [np.asarray(x) for x in dets]
                for j, i in enumerate(group):
                    out[i].append((head[j], tuple(x[j] for x in dets)))
    return out


# a served detection matches a reference one of the same class when its box
# and score lie within DET_TOL × max(1, |reference value|) of the reference's:
# a head within the configurations' ``head_gap`` limit (1e-3) moves a box or
# score by about that limit at most, while a wrong grid cell moves a box by
# 1/32 or more
DET_TOL = 1e-2


def unmatched(got: tuple, want: tuple) -> int:
    """Detections of one frame, on either side, with no partner on the
    other. ``got``, ``want``: (boxes (D, 4), scores (D,), classes (D,),
    valid (D,)). NMS leaves no two same-class detections this close, so a
    greedy pairing is a maximal one."""
    def live(det):
        valid = np.asarray(det[3], bool)
        return (np.asarray(det[0]).reshape(len(valid), 4)[valid],
                np.asarray(det[1]).reshape(-1)[valid],
                np.asarray(det[2]).reshape(-1)[valid])

    (gb, gs, gc), (wb, ws, wc) = live(got), live(want)
    close = gc[:, None] == wc[None, :]
    close &= np.abs(gs[:, None] - ws[None, :]) <= DET_TOL * np.maximum(1.0, np.abs(ws))[None, :]
    close &= (np.abs(gb[:, None, :] - wb[None, :, :])
              <= DET_TOL * np.maximum(1.0, np.abs(wb))[None, :, :]).all(axis=-1)
    taken = np.zeros(len(ws), bool)
    pairs = 0
    for row in close:
        free = np.flatnonzero(row & ~taken)
        if free.size:
            taken[free[0]] = True
            pairs += 1
    return len(gs) + len(ws) - 2 * pairs


def compare(served: list, expected: list) -> dict:
    """Served (head, detections) per frame against the reference's, for
    matching lists of clips. ``head_gap``: the largest |served − reference|
    head value (the float32 maximum where a served value is not finite);
    ``det_mismatch``: detections, served or reference, with no partner of
    the same class and a box and score within ``DET_TOL`` on the other
    side, summed over the frames."""
    gap = 0.0
    mismatch = 0
    frames = 0
    for got_clip, want_clip in zip(served, expected):
        if len(got_clip) != len(want_clip):
            raise ValueError(f"clip of {len(want_clip)} frames served "
                             f"{len(got_clip)}")
        for (g_head, g_det), (w_head, w_det) in zip(got_clip, want_clip):
            frames += 1
            g_head = np.asarray(g_head, np.float32)
            if not np.isfinite(g_head).all():  # a finite stand-in keeps the line JSON
                gap = float(np.finfo(np.float32).max)
            else:
                gap = max(gap, float(np.abs(g_head - w_head).max()))
            mismatch += unmatched(tuple(g_det), tuple(w_det))
    return {"head_gap": gap, "det_mismatch": mismatch, "frames": frames}
