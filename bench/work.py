"""The work one frame asks of the chip, counted from the weights and shapes.

For each fused conv layer (every layer but the head), per frame:

* ops = 2 × nnz × H × W × t_in — one multiply and one add per nonzero int8
  weight and output pixel, at each input time step the conv is evaluated
  at (1 for ``encode``, 1 for ``conv_block`` under the mixed schedule, the
  full T otherwise);
* bytes = input activations at 1 byte (a spike or a u8 pixel) × t_in
  + nnz weight bytes + one bitmask bit per dense weight position
  + output spikes at 1 byte × t_out + the membrane read and written at
  4 bytes each;
* roofline time = max(ops ÷ int8 peak, bytes ÷ HBM bandwidth).

Binary spikes and u8 pixels times int8 weights are integer products, so
the int8 peak is the right ceiling: no implementation of this work can
exceed it. The counts depend on the nonzero weights and the layer shapes
only, never on tiling, dtype or layout. The head's 1×1 conv counts toward
the whole step's ops (``step_ops``) and not toward the fused kernels.
"""
from __future__ import annotations

import numpy as np

from reference import Layer, Net, layers


def layer_work(lay: Layer, nnz: int) -> tuple[int, int]:
    """(ops, bytes) of one layer for one frame."""
    ops = 2 * nnz * lay.h * lay.w * lay.t_in
    dense = lay.k * lay.k * lay.cin * lay.cout
    act_in = lay.h * lay.w * lay.cin * lay.t_in
    out_spikes = lay.h * lay.w * lay.cout * lay.t_out
    membrane = 2 * 4 * lay.h * lay.w * lay.cout
    return ops, act_in + nnz + dense // 8 + out_spikes + membrane


def count(net: Net, nnz: dict, peaks: dict) -> dict:
    """Per-frame work of the network. ``nnz``: nonzero weights per layer
    name. Returns the fused layers' ops, bytes and roofline seconds, and the
    whole step's ops (fused layers and head)."""
    fused_ops = fused_bytes = 0
    roofline_s = 0.0
    step_ops = 0
    per_layer = {}
    for lay in layers(net):
        ops, nbytes = layer_work(lay, int(nnz[lay.name]))
        step_ops += ops
        if lay.name == "head":
            continue
        t = max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
        per_layer[lay.name] = {"ops": ops, "bytes": nbytes, "roofline_s": t}
        fused_ops += ops
        fused_bytes += nbytes
        roofline_s += t
    return {"fused_ops": fused_ops, "fused_bytes": fused_bytes,
            "fused_roofline_s": roofline_s, "step_ops": step_ops,
            "layers": per_layer}


def nonzeros(ref_weights: dict) -> dict:
    """Nonzero quantized weights per layer, from the reference's own
    quantized copy of the weights."""
    return {name: int(np.count_nonzero(np.asarray(wl["q"])))
            for name, wl in ref_weights.items()}
