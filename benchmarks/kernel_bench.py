"""Kernel microbenchmarks (§III): the Pallas gated one-to-all conv, the
fused LIF scan, and the bitmask matmul, validated in interpret mode against
their jnp oracles, with the accounting the ASIC exposes in hardware:

  * cycle model: taps executed = nnz weights (zero-weight skipping),
  * compressed weight bytes read vs dense (bit-mask format),
  * fused-LIF: membrane potential never round-trips HBM between time steps.

``--fast`` runs only the fused layer-pipeline smoke: full-forward parity of
the fused conv→tdBN→LIF kernel against the jitted dense oracle (bit-exact,
exits nonzero on any mismatch) plus the encoding-layer dispatch-count
assertion — the 8 bit-serial planes must fold into ONE ``pallas_call``.
CI runs this under ``JAX_PLATFORMS=cpu`` as the kernel-bench gate.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def run_fused() -> dict:
    """Fused layer-pipeline gate at the reduced e2e scale: dense-oracle
    parity (bit-exact) and the single-dispatch bit-serial encode."""
    import dataclasses

    from benchmarks.e2e_detector import reduced_config
    from repro.core import plan as cplan, pruning
    from repro.kernels import backend
    from repro.models import snn_yolo as sy

    cfg = reduced_config()
    params, bn = sy.init_params(jax.random.PRNGKey(0), cfg)
    params = pruning.prune_tree(params, 0.8)
    rng = np.random.default_rng(0)
    h, w_ = cfg.input_hw
    imgs = jnp.asarray(rng.integers(0, 256, (1, h, w_, 3)) / 255.0, jnp.float32)
    bn = sy.calibrate_bn_state(params, bn, imgs, cfg)

    heads = {}
    det = None
    for ex in ("dense", "pallas"):
        det = sy.compile_detector(dataclasses.replace(cfg, conv_exec=ex),
                                  params, bn)
        _, head = det.detect(imgs)
        heads[ex] = np.asarray(head)
    err = float(np.abs(heads["pallas"] - heads["dense"]).max())
    print(f"fused_pipeline   : err={err:.2e} (pallas vs jitted dense oracle)")
    assert err == 0.0, f"fused pipeline diverges from dense oracle: {err}"

    # the encoding layer must be ONE dispatch: 8 bit planes folded by conv
    # linearity into a single lane-dense pallas_call, not 8 serial sweeps
    pcfg = dataclasses.replace(cfg, conv_exec="pallas")
    lp = det.plan.layers["encode"]
    spec = next(s for s in sy.layer_specs(pcfg) if s.name == "encode")
    x_t = imgs[None]  # (t_in=1, N, H, W, 3)

    def encode_layer(x):
        return cplan.run_encode(
            x, lp, pcfg,
            gamma=params["encode"]["gamma"], beta=params["encode"]["beta"],
            mean=bn["encode"]["mean"], var=bn["encode"]["var"],
            v0=None, out_t=spec.t_out)

    n_calls = backend.count_pallas_calls(encode_layer, x_t)
    print(f"encode dispatches: {n_calls} (8 bit planes, one fused kernel)")
    assert n_calls == 1, f"bit-serial encode must be 1 dispatch, got {n_calls}"
    return {"fused_pipeline": {"max_err": err, "encode_dispatches": n_calls}}


def run() -> dict:
    key = jax.random.PRNGKey(0)
    out = {}

    # --- gated one-to-all product (sparse spike conv) ---
    n, h, w_, cin, cout, density = 1, 18, 32, 32, 64, 0.2
    x = (jax.random.uniform(key, (n, h, w_, cin)) < 0.25).astype(jnp.int8)
    wd = np.array(jax.random.randint(jax.random.PRNGKey(1), (3, 3, cin, cout), -127, 127, jnp.int8))
    wd[np.random.default_rng(0).random(wd.shape) > density] = 0
    packed = ops.pack_conv_weights(wd)
    t0 = time.time()
    y = ops.gated_conv(x, packed, interpret=True)
    t_k = time.time() - t0
    y_ref = ref.gated_conv_ref(x, jnp.asarray(wd))
    err = int(jnp.max(jnp.abs(y.astype(jnp.int32) - y_ref.astype(jnp.int32))))
    nnz = int((wd != 0).sum())
    out["gated_one_to_all"] = {
        "max_err": err,
        "nnz_taps": nnz,
        "dense_taps": int(wd.size),
        "cycle_saving": 1 - nnz / wd.size,
        "weight_bytes_dense": int(wd.size),
        "weight_bytes_compressed": int(packed.compressed_bytes),
        "interpret_s": t_k,
    }
    print(f"gated_one_to_all : err={err} cycle_saving={out['gated_one_to_all']['cycle_saving']*100:.1f}% "
          f"bytes {packed.compressed_bytes}/{wd.size}")
    assert err == 0, "kernel must be exact vs oracle"

    # --- fused LIF ---
    t, m, c = 4, 512, 32
    cur = jax.random.normal(key, (t, m, c), jnp.float32)
    s_k = ops.fused_lif(cur, threshold=0.5, leak=0.25, interpret=True)
    s_r = ref.fused_lif_ref(cur, threshold=0.5, leak=0.25)
    lif_err = float(jnp.max(jnp.abs(s_k.astype(jnp.float32) - s_r.astype(jnp.float32))))
    out["fused_lif"] = {"max_err": lif_err, "spike_rate": float(jnp.mean(s_k.astype(jnp.float32)))}
    print(f"fused_lif        : err={lif_err} rate={out['fused_lif']['spike_rate']:.3f}")

    # --- bitmask matmul ---
    mm, kk, nn = 64, 512, 256
    w2 = np.array(jax.random.normal(jax.random.PRNGKey(2), (kk, nn)), np.float32)
    w2[np.abs(w2) < 1.2] = 0.0  # ~77% sparse (paper's weight regime)
    xs = jax.random.normal(jax.random.PRNGKey(3), (mm, kk), jnp.float32)
    pw = ops.pack_matmul_weights(w2)
    y2 = ops.bitmask_matmul(xs, pw, interpret=True)
    y2_ref = ref.bitmask_matmul_ref(xs, jnp.asarray(w2))
    mm_err = float(jnp.max(jnp.abs(y2 - y2_ref)))
    out["bitmask_matmul"] = {
        "max_err": mm_err,
        "density": float((w2 != 0).mean()),
        "compressed_bytes": int(pw.compressed_bytes),
        "dense_bytes": int(w2.size * 4),
    }
    print(f"bitmask_matmul   : err={mm_err:.2e} density={out['bitmask_matmul']['density']:.2f} "
          f"bytes {pw.compressed_bytes}/{int(w2.size*4)}")
    out.update(run_fused())
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true",
                    help="fused-pipeline smoke only (parity + dispatch "
                    "count) — the CI kernel-bench gate")
    args = ap.parse_args(argv)
    return run_fused() if args.fast else run()


if __name__ == "__main__":
    main()
