"""The work one decode step asks of the chip, counted from the model's shapes
and the live lengths the harness recorded, never from the program's tiling
or cache layout: the same work is read whatever implements it.

For a dense decoder (``dense_decode``), one step over ``slots`` sequences
that attend to ``live_positions`` cached positions in all:

* flops = 2 × matrix weights outside the embedding × slots (q, k, v, o,
  gate, up, down and the q/k/v biases' adds counted as weights)
  + 2 × hidden × vocab × slots (the head)
  + 4 × layers × heads × head_dim × live positions (q·k and p·v);
* bytes = every weight once at the served width (the embedding read whole
  by the tied head) + k and v of every live position of every layer.

A program that reads its whole ``(layers, slots, max_seq, kv, hd)`` cache
each step, or copies it, does more than this and shows a low roofline
share, not more work.
"""
from __future__ import annotations


def dense_params(s: dict) -> tuple[int, int]:
    """(matrix and bias weights outside the embedding, all weights)."""
    d, hd = s["hidden"], s["head_dim"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    attn = d * q + 2 * d * kv + q * d + ((q + 2 * kv) if s["qkv_bias"] else 0)
    mlp = 3 * d * s["ffn"]
    body = s["layers"] * (attn + mlp)
    norms = s["layers"] * 2 * d + d
    embed = s["vocab"] * d * (1 if s["tied"] else 2)
    return body, body + norms + embed


def dense_decode(s: dict, dtype_bytes: int, slots: int, live_positions: int) -> dict:
    """Flops and bytes of one decode step of a dense decoder."""
    body, total = dense_params(s)
    head = s["hidden"] * s["vocab"]
    attn = 4 * s["layers"] * s["heads"] * s["head_dim"] * live_positions
    kv_bytes = 2 * s["layers"] * s["kv_heads"] * s["head_dim"] * dtype_bytes * live_positions
    return {"flops": 2 * body * slots + 2 * head * slots + attn,
            "bytes": total * dtype_bytes + kv_bytes}
