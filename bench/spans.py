"""Engine spans and layer scopes of one cell, on the chip.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> [--keep <dir>]

What ``run.py --trace 1`` reads of the device, plus what the program's own
tracer (``repro.serve.trace``) records of the host, on one clock. One
process and one set-up; then windows of ``--seconds`` each, every one on a
fresh engine after the warm-up, in this order: tracer off, tracer on,
tracer off, tracer on (all with the profiler off: the tracer's cost is the
tick median with it on against off), then tracer on with the device-only
profiler on part of the window, as ``run.py --trace 1`` takes it.

The last line of standard output is one JSON object: the tick medians of
each window, the per-layer metrics (those of ``BENCHMARK.json`` and the
readers ``dispatch_ms``, ``upload_ms``, ``copyout_ms`` and ``encode_ms``,
which read the spans and the layer scopes), the breakdown
(``idle_by_span``, ``device_scopes``), the clock check, the tracer's
counters in the traced window and its summary of an untraced window.
``--keep`` copies the trace and the serving step's compiled HLO text there.
The correctness check is ``run.py``'s; this script makes none. It exits
nonzero without a TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import reference as ref  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import trace_spans  # noqa: E402

det = run.load_kind("detector")

NEW_METRICS = ("dispatch_ms", "upload_ms", "copyout_ms", "encode_ms")
WINDOWS = ("off", "on", "off", "on")


def traced_spans(spans, first: int, end: int) -> list:
    """The spans of the ticks with ids in [first, end) and their children,
    as (name, start_ns, end_ns, parent) with parents indexed anew."""
    new_index: dict = {}
    out = []
    for i, s in enumerate(spans):
        if s.parent < 0:
            keep = s.name == trace_spans.ROOT_SPAN and first <= s.ids.get("tick", -1) < end
        else:
            keep = s.parent in new_index
        if keep:
            new_index[i] = len(out)
            out.append((s.name, s.start_ns, s.end_ns, new_index.get(s.parent, -1)))
    return out


def serving_hlo(cell) -> str:
    """The compiled serving step's HLO text at the cell's bucket (a
    persistent-cache hit after the windows)."""
    import jax
    import jax.numpy as jnp

    core = cell.core
    h, w = cell.net.input_hw
    frames = jax.ShapeDtypeStruct((core.cap, h, w, 3), jnp.float32)
    mask = jax.ShapeDtypeStruct((core.cap,), jnp.bool_)
    det = core.det
    return det._masked_step_fn.lower(det.params, det.bn_state, frames, core._mem,
                                     mask, mask).compile().as_text()


def measure(cfg_doc: dict, traffic: dict, seed: int, seconds: float, peaks: dict,
            per_layer: list, program, *, keep: str | None = None, log=sys.stderr) -> dict:
    from repro.serve.trace import Tracer

    cell = det.Cell(cfg_doc, traffic, program)
    medians: dict = {"off": [], "on": []}
    summary = None
    for mode in WINDOWS:
        cell.start(seed)
        cell.warm_up()
        tracer = Tracer(enabled=mode == "on")
        cell.core.tracer = tracer
        win = det.run_window(cell, seconds)
        medians[mode].append(float(np.median([1e3 * (t[1] - t[0]) for t in win.ticks])))
        if mode == "on":
            summary = tracer.summary()
        print(f"window tracer {mode}: {len(win.ticks)} ticks, median tick "
              f"{medians[mode][-1]:.3f} ms", file=log)

    cell.start(seed)
    cell.warm_up()
    tracer = Tracer(enabled=True)
    cell.core.tracer = tracer
    tmp = tempfile.TemporaryDirectory(prefix="bench-spans-")
    try:
        profile = run.ProfileWindow(tmp.name, seconds)
        win = det.run_window(cell, seconds, profile)
        traced = (profile.first, profile.end)
        traced_ms = float(np.median([1e3 * (t[1] - t[0]) for t in win.ticks]))
        counters = dict(tracer.counters)
        gauges = dict(tracer.gauges)
        spans = traced_spans(tracer.spans, traced[0], traced[1])
        hlo = serving_hlo(cell)
        names = [lay.name for lay in ref.layers(cell.net)]
        scopes = trace_spans.layer_scopes(names)
        red = trace_reduce.reduce_dir(tmp.name, det.STEP_MODULE)
        extra = trace_spans.reduce_dir(tmp.name, spans, scopes,
                                       trace_spans.hlo_op_paths(hlo))
        if keep:
            Path(keep).mkdir(parents=True, exist_ok=True)
            for i, p in enumerate(glob.glob(f"{tmp.name}/**/*.xplane.pb", recursive=True)):
                shutil.copy(p, Path(keep) / f"trace{i}.xplane.pb")
            (Path(keep) / "serving_step.hlo.txt").write_text(hlo)
            (Path(keep) / "spans.json").write_text(json.dumps(spans))
    finally:
        tmp.cleanup()

    ticks = win.ticks[traced[0]:traced[1]]
    ctx = {**det.trace_context(cell, win, {**red, **extra}, ticks, peaks),
           "spans": spans, "counters": counters}
    metrics = {}
    for name in [m["name"] for m in per_layer] + list(NEW_METRICS):
        value = run.load_reader(name)(ctx)
        if value is not None:
            metrics[name] = value
    scoped = extra["by_scope"]
    total = sum(sum(v.values()) for v in scoped.values())
    idle = extra["idle_by_span"]
    off, on = float(np.median(medians["off"])), float(np.median(medians["on"]))
    return {
        "tick_ms": {"off": medians["off"], "on": medians["on"], "traced": traced_ms},
        "tracer_cost_pct": 100.0 * (on - off) / off,
        "metrics": metrics,
        "breakdown": {"idle_by_span": idle, "device_scopes": scoped},
        "scoped_share": 1.0 - sum(scoped.get(trace_spans.UNSCOPED, {}).values()) / total
        if total else None,
        "layers_with_kernel": sum(1 for n in names if scoped.get(n, {}).get("kernel_ms")),
        "named_idle_share": 1.0 - idle.get(trace_spans.OUTSIDE, 0.0) / sum(idle.values())
        if idle else None,
        "clock": extra["clock"],
        "profile_start_ns": extra["profile_start_ns"],
        "counters": counters,
        "gauges": gauges,
        "summary_untraced": summary,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    try:
        spec = run.load_json(run.ROOT / "BENCHMARK.json")
        cell = run.find_cell(spec, args.workload)
        cfg_doc = run.load_json(BENCH / "configs" / f"{cell['config']}.json")
        traffic = run.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
        program = run.import_program(det)
        run.enable_cache()
        import jax

        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise run.BenchError("JAX found no TPU")
        peaks = run.load_peaks(dev.device_kind)
        out = measure(cfg_doc, traffic, args.seed, args.seconds, peaks,
                      run.cell_metrics(spec["per_layer"], args.workload), program,
                      keep=args.keep)
    except run.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
