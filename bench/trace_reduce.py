"""From a JAX profiler trace of a serving window to the per-layer numbers.

What the TPU trace holds (read by hand from a v5e trace): the device plane
``/device:TPU:<n>`` has a line ``XLA Modules`` with one event per execution
of a compiled program (the serving step is ``jit__masked(<hash>)``) and a
line ``XLA Ops`` with one event per HLO operation, named by its HLO text
(``%name = type op(...), ...``). A Pallas kernel is a ``custom-call`` whose
text names the target ``tpu_custom_call`` (the fused layers appear as
``_dispatch_fused.<n>``); XLA's own custom calls (``ConcatBitcast``) are
not kernels. Asynchronous copies sit on their own line and overlap the
operations; they do not count as busy. Times are in nanoseconds.

The trace is taken with host tracing off: the TPU runtime records every
chunk of its host-side layout transposes at the lowest host level (about a
million events per second of a fleet window), which slowed a traced tick
from about 0.12 s to 0.6 s. The window is therefore set by the device
itself: from the start of the first traced serving step to the start of
the last, so it holds whole tick periods.

:func:`reduce` works on plain event lists, so it is tested on hand-built
ones; :func:`reduce_dir` reads a trace directory and averages over the
device planes.
"""
from __future__ import annotations

import glob
import os
from typing import NamedTuple

STEP_MODULE = "jit__masked"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
TOP = 10
INSIDE = "inside a serving step"
BETWEEN = "between serving steps (engine host work)"


class Events(NamedTuple):
    """One device's events. Each event is (name, start_ns, end_ns)."""

    ops: list
    modules: list


def is_kernel(op_name: str) -> bool:
    return KERNEL_TARGET in op_name


def short_name(op_name: str) -> str:
    """The HLO instruction name of an op event, without its text."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def union_ns(intervals) -> int:
    """Length of the union of (start, end) intervals."""
    total = 0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo, hi):
    """Idle stretches of [lo, hi] outside the union of intervals."""
    out = []
    t = lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def reduce(ev: Events) -> dict:
    """Per-step and window numbers of one device.

    Steps are the executions of the serving program; the window runs from
    the first step's start to the last step's start, and the steps counted
    are those that start inside it. A step's ops are those that start
    inside its execution. Busy time is the union of all op intervals
    clipped to the window. Idle gaps are named by whether they fall inside
    a step's execution or between steps."""
    steps = sorted((s, e) for n, s, e in ev.modules if n.startswith(STEP_MODULE))
    if len(steps) < 2:
        return empty()
    lo, hi = steps[0][0], steps[-1][0]
    steps = steps[:-1]
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ev.ops if e > lo and s < hi]
    busy = union_ns((s, e) for _, s, e in ops)
    starts = sorted((o for o in ev.ops if lo <= o[1] < hi), key=lambda o: o[1])
    step_busy, kernel_ns, other_ns = [], 0, 0
    i = 0
    for ms, me in steps:
        while i < len(starts) and starts[i][1] < ms:
            i += 1
        j = i
        while j < len(starts) and starts[j][1] < me:
            j += 1
        inside = starts[i:j]
        step_busy.append(union_ns((s, e) for _, s, e in inside))
        for name, s, e in inside:
            if is_kernel(name):
                kernel_ns += e - s
            else:
                other_ns += e - s
    by_op: dict = {}
    for name, s, e in ops:
        key = short_name(name)
        by_op[key] = by_op.get(key, 0) + e - s
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = []
    for s, e in _gaps([(s, e) for _, s, e in ops], lo, hi):
        mid = (s + e) / 2
        where = INSIDE if any(ms <= mid < me for ms, me in steps) else BETWEEN
        gaps.append((where, e - s))
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_ns": hi - lo, "busy_ns": busy, "n_steps": len(steps),
        "step_busy_ns": step_busy, "kernel_ns": kernel_ns, "other_ns": other_ns,
        "top_ops": [[n, ns / 1e9] for n, ns in top_ops],
        "idle_gaps": [[n, ns / 1e9] for n, ns in gaps[:TOP]],
    }


def empty() -> dict:
    return {"window_ns": 0, "busy_ns": 0, "n_steps": 0, "step_busy_ns": [],
            "kernel_ns": 0, "other_ns": 0, "top_ops": [], "idle_gaps": []}


def _events(line) -> list:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str) -> list:
    """Events per TPU device plane of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    devices = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" in lines and "XLA Modules" in lines:
            devices.append(Events(_events(lines["XLA Ops"]),
                                  _events(lines["XLA Modules"])))
    return devices


def reduce_dir(trace_dir: str) -> dict:
    """Reduce the trace written under ``trace_dir``, averaged over the
    device planes that ran the serving step."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    reds = [reduce(ev) for ev in (load(paths[0]) if paths else [])]
    reds = [r for r in reds if r["n_steps"]]
    if not reds:
        return empty()
    out = dict(reds[0])
    n = len(reds)
    for key in ("window_ns", "busy_ns", "n_steps", "kernel_ns", "other_ns"):
        out[key] = sum(r[key] for r in reds) / n
    out["step_busy_ns"] = [x for r in reds for x in r["step_busy_ns"]]
    return out
