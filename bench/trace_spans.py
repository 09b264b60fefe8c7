"""The program's own spans and layer scopes laid over a device trace.

``trace_reduce.py`` reads the device alone. This module adds what the
program itself records:

* the clock: the program stamps its spans with ``time.time_ns()``; a
  device event of the trace starts at the trace's ``profile_start_time``
  (a stat of its ``Task Environment`` plane, epoch ns) plus its own
  ``start_ns``. :func:`profile_start_ns` reads it.
* layer scopes: the model runs each layer under ``jax.named_scope(<layer
  name>)`` and the pools, head, postprocess and masking under scopes of
  their own, so each op's ``op_name`` in the compiled serving step's HLO
  (``jit(_masked)/encode/...``) names its layer; the trace's op events are
  named by instruction. :func:`by_scope` gives each scope's device time per
  serving step, kernels and glue apart.
* idle gaps: :func:`idle_by_span` splits each idle gap of the device
  window (the gaps ``trace_reduce`` names) over the innermost engine span
  that overlaps it; time no engine span covers is ``outside_engine``.
* :func:`clock_check`: each serving step should start after its tick's
  ``dispatch`` span starts and end before its ``block`` span ends.

Spans are plain tuples ``(name, start_ns, end_ns, parent)`` with
``parent`` an index into the same list (-1 for none), as the program's
``repro.serve.trace.Tracer`` keeps them. Only spans under a ``tick`` span
are engine work; a request's ``queued`` span is not.
"""
from __future__ import annotations

import glob
import os
import re
import statistics

import trace_reduce as tr

ROOT_SPAN = "tick"
OUTSIDE = "outside_engine"
UNSCOPED = "unscoped"
# scopes the model puts around what is not a fused layer, beside the
# layers' own names (``reference.layers``)
EXTRA_SCOPES = ("head", "postprocess", "mask") + tuple(f"pool{k}" for k in range(8))
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


# ------------------------------------------------------------------ clock --


def profile_start_ns(planes) -> int | None:
    """``profile_start_time`` of the ``Task Environment`` plane."""
    for plane in planes:
        if plane.name == "Task Environment":
            for name, value in plane.stats:
                if name == "profile_start_time":
                    return int(value)
    return None


# ----------------------------------------------------------------- scopes --


def hlo_op_paths(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` of a compiled module's HLO text. The
    TPU trace's op events carry no ``op_name`` (their stats are offsets and
    durations), only the instruction's text. An instruction XLA added
    itself (a layout ``copy``, a broadcast of a constant) has no
    ``op_name``: it takes that of its first operand that has one, so a
    layout copy counts for the layer whose output it moves."""
    out: dict = {}
    operands: dict = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if " = " not in line or not line.startswith(("%", "ROOT %")):
            continue
        name = tr.short_name(line.removeprefix("ROOT "))
        m = _OP_NAME.search(line)
        if m:
            out[name] = m.group(1)
        else:
            operands[name] = _OPERAND.findall(line.split(" = ", 1)[1])
    for _ in range(8):  # operands defined after their users: a few passes
        found = {}
        for name, ops in operands.items():
            path = next((out[o] for o in ops if o in out), None)
            if path is not None:
                found[name] = path
        if not found:
            break
        out.update(found)
        for name in found:
            del operands[name]
    return out


def scope_of(path: str, scopes) -> str:
    """The longest of ``scopes`` that ``path`` starts with once its leading
    ``jit(...)`` components (the serving step itself) are dropped."""
    parts = path.split("/")
    while parts and parts[0].startswith("jit("):
        parts = parts[1:]
    best = UNSCOPED
    best_len = 0
    for scope in scopes:
        sp = scope.split("/")
        if len(sp) > best_len and parts[:len(sp)] == sp:
            best, best_len = scope, len(sp)
    return best


def step_ops(ops, modules):
    """(steps, ops that start inside a step): the serving steps inside the
    window that ``trace_reduce.reduce`` uses, and their ops. Each op is
    (name, start, end, path)."""
    steps = sorted((s, e) for n, s, e in modules if n.startswith(tr.STEP_MODULE))
    if len(steps) < 2:
        return [], []
    lo, hi = steps[0][0], steps[-1][0]
    steps = steps[:-1]
    starts = sorted((o for o in ops if lo <= o[1] < hi), key=lambda o: o[1])
    inside, i = [], 0
    for ms, me in steps:
        while i < len(starts) and starts[i][1] < ms:
            i += 1
        j = i
        while j < len(starts) and starts[j][1] < me:
            j += 1
        inside += starts[i:j]
        i = j
    return steps, inside


def by_scope(ops, modules, scopes) -> dict:
    """Device ms per serving step of each scope: ``{scope: {"kernel_ms",
    "glue_ms"}}``, ops summed (as ``kernel_ms`` and ``xla_ops_ms`` are);
    ops of no known scope go under ``unscoped``."""
    steps, inside = step_ops(ops, modules)
    if not steps:
        return {}
    out: dict = {}
    for name, s, e, path in inside:
        entry = out.setdefault(scope_of(path, scopes), {"kernel_ms": 0.0, "glue_ms": 0.0})
        entry["kernel_ms" if tr.is_kernel(name) else "glue_ms"] += (e - s) / 1e6
    for entry in out.values():
        entry["kernel_ms"] /= len(steps)
        entry["glue_ms"] /= len(steps)
    return dict(sorted(out.items(), key=lambda kv: -sum(kv[1].values())))


# ------------------------------------------------------------------ spans --


def engine_segments(spans) -> list:
    """The engine's time as non-overlapping (start, end, name) segments,
    each named by the innermost span under a ``tick`` that covers it."""
    depth: dict = {}

    def depth_of(i):
        if i not in depth:
            name, _, _, parent = spans[i]
            if parent < 0:
                depth[i] = 0 if name == ROOT_SPAN else None
            else:
                d = depth_of(parent)
                depth[i] = None if d is None else d + 1
        return depth[i]

    eng = [(s, e, depth_of(i), n) for i, (n, s, e, _) in enumerate(spans)
           if depth_of(i) is not None and e > s]
    points = sorted({p for s, e, _, _ in eng for p in (s, e)})
    segs = []
    for a, b in zip(points, points[1:]):
        covering = [(d, n) for s, e, d, n in eng if s <= a and b <= e]
        if covering:
            name = max(covering)[1]
            if segs and segs[-1][2] == name and segs[-1][1] == a:
                segs[-1] = (segs[-1][0], b, name)
            else:
                segs.append((a, b, name))
    return segs


def idle_by_span(gaps, spans, offset_ns: int) -> dict:
    """Seconds of the device's idle ``gaps`` ((start, end) on the trace's
    clock) under each innermost engine span; ``offset_ns`` takes the trace's
    clock to the spans' (``profile_start_ns``)."""
    segs = engine_segments(spans)
    out: dict = {}
    for gs, ge in gaps:
        gs, ge = gs + offset_ns, ge + offset_ns
        covered = 0
        for s, e, name in segs:
            lo, hi = max(s, gs), min(e, ge)
            if hi > lo:
                out[name] = out.get(name, 0) + hi - lo
                covered += hi - lo
        if ge - gs > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0) + (ge - gs - covered)
    return {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def clock_check(modules, spans, offset_ns: int) -> dict:
    """For each serving step: the tick whose ``dispatch`` span last started
    before the step did; the step passes when it ends before that tick's
    ``block`` span ends. Returns the share that pass and the lag from the
    step's end to the block's end (ms, median and max over those that pass)."""
    by_tick: dict = {}
    for n, s, e, parent in spans:
        if n in ("dispatch", "block") and parent >= 0:
            by_tick.setdefault(parent, {})[n] = (s, e)
    pairs = sorted(v["dispatch"] + v["block"] for v in by_tick.values()
                   if "dispatch" in v and "block" in v)
    steps = sorted((s + offset_ns, e + offset_ns) for n, s, e in modules
                   if n.startswith(tr.STEP_MODULE))
    ok, lags = 0, []
    for ms, me in steps:
        before = [p for p in pairs if p[0] <= ms]
        if before and me <= before[-1][3]:
            ok += 1
            lags.append((before[-1][3] - me) / 1e6)
    return {"steps": len(steps), "inside": ok,
            "share": ok / len(steps) if steps else None,
            "lag_ms_median": statistics.median(lags) if lags else None,
            "lag_ms_max": max(lags) if lags else None}


# ------------------------------------------------------------------- file --


def load(path: str, hlo_paths: dict):
    """(profile_start_ns, [(ops, modules)] per TPU device plane) of one
    ``.xplane.pb``; each op is (name, start, end, op path), the path looked
    up in ``hlo_paths`` (:func:`hlo_op_paths`) by instruction name."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    devices = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        ops = [(name, s, e, hlo_paths.get(tr.short_name(name), ""))
               for name, s, e in tr._events(lines["XLA Ops"])]
        devices.append((ops, tr._events(lines["XLA Modules"])))
    return profile_start_ns(planes), devices


def reduce_dir(trace_dir: str, spans, scopes, hlo_paths: dict) -> dict:
    """``profile_start_ns``, ``by_scope``, ``idle_by_span`` and ``clock`` of
    the first device plane that ran the serving step, under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    empty = {"profile_start_ns": None, "by_scope": {}, "idle_by_span": {}, "clock": {}}
    if not paths:
        return empty
    start, devices = load(paths[0], hlo_paths)
    for ops, modules in devices:
        steps = sorted((s, e) for n, s, e in modules if n.startswith(tr.STEP_MODULE))
        if len(steps) < 2:
            continue
        lo, hi = steps[0][0], steps[-1][0]
        busy = [(max(s, lo), min(e, hi)) for _, s, e, _ in ops if e > lo and s < hi]
        out = {"profile_start_ns": start, "by_scope": by_scope(ops, modules, scopes)}
        if start is not None:
            out["idle_by_span"] = idle_by_span(tr._gaps(busy, lo, hi), spans, start)
            out["clock"] = clock_check(modules, spans, start)
        else:
            out["idle_by_span"], out["clock"] = {}, {}
        return out
    return empty


def per_tick_ms(spans, names) -> float | None:
    """Total ms of the spans named in ``names`` over the number of ``tick``
    spans among ``spans`` (None without a tick)."""
    ticks = sum(1 for s in spans if s[0] == ROOT_SPAN)
    if not ticks:
        return None
    return sum(e - s for n, s, e, _ in spans if n in names) / ticks / 1e6


def layer_scopes(layer_names) -> list:
    """Every scope the model puts on its ops: the layers' names, each CSP
    stage's concat, and :data:`EXTRA_SCOPES`."""
    concat = [n[:-len("/agg")] + "/concat" for n in layer_names if n.endswith("/agg")]
    return list(layer_names) + concat + list(EXTRA_SCOPES)
