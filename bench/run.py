"""Chip benchmark of the program's serving paths.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness reads ``bench/configs/<config>.json`` and ``bench/traffic/<traffic>
.json``, the driver of the configuration's kind, ``bench/kinds/<kind>.py``
(``"kind"`` in the configuration, ``"detector"`` where it names none), and,
for ``--trace 1``, one reader ``bench/metrics/<metric>.py`` per per-layer
metric of the cell, all found by name.

This file holds what every kind shares: the command line, the device and
chip-count checks, the peaks (``peaks.json``), the persistent compilation
cache, the compile counter, the set-up clock, the profiler window and the
result line. A kind driver provides (``kinds/detector.py``, ``kinds/lm.py``):

* ``STEP_MODULE``: the name of its serving step's module in the device
  trace, from which ``trace_reduce`` takes the steps;
* ``program()``: imports the parts of the program it drives;
* ``validate(cfg_doc, traffic)``: raises ValueError on a configuration or
  traffic mix it cannot run;
* ``Cell(cfg_doc, traffic, program)`` with ``start(seed)``, ``warm_up()``
  and ``stop()``: set-up, the run's inputs from the seed, the warm-up of
  every shape the window uses, and the release of the program's state;
* ``run_window(cell, seconds, profile)``: the timed window, which calls the
  :class:`ProfileWindow` around each tick; it returns a window with
  ``ticks``, ``attempted`` and ``failed``;
* ``end_to_end(win)`` and ``summary(win)``: the end-to-end metrics and a
  line for standard error;
* ``verify(cell, win)``: frees the program's state, then compares a sample
  of what the window served with the plain reference; returns ``(ok,
  checks)``, each compared number beside its limit;
* ``trace_context(cell, win, red, ticks, peaks)``: what its per-layer
  readers read.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``checks`` last: each compared number with its limit).
The harness exits nonzero, and prints no such line, when JAX finds no TPU or
fewer chips than the cell asks for, when the program's sources are not in
the checkout, or when a name it looks up has no file.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

CACHE_DIR = ROOT / ".jax_cache"
# the traced part of a --trace 1 window: it starts this far into the
# window and lasts this long (both shortened for short windows)
TRACE_START_S = 1.0
TRACE_LEN_S = 3.0


class BenchError(Exception):
    """The benchmark cannot run here; nothing is printed on stdout."""


# ------------------------------------------------------------------ loading --


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"{path} not found") from None


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(entries: list, cell: str) -> list:
    """The metric entries that apply to ``cell``: those without a
    ``workloads`` key and those that list it."""
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


_MODULES: dict = {}


def load_module(folder: str, name: str, what: str):
    """The module ``bench/<folder>/<name>.py``, loaded from its file once."""
    path = BENCH / folder / f"{name}.py"
    if path not in _MODULES:
        if not path.is_file():
            raise BenchError(f"no {what} {path} for {name!r}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{folder}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def load_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return load_module("metrics", name, "reader").read


def kind_of(cfg_doc: dict) -> str:
    return cfg_doc.get("kind", "detector")


def load_kind(name: str):
    """The driver ``bench/kinds/<name>.py`` of a kind of system."""
    return load_module("kinds", name, "kind driver")


def load_peaks(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def import_program(kind):
    """Put the checkout's ``src/`` first on the path and import the parts
    of the program that the kind driver ``kind`` drives."""
    src = ROOT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    try:
        return kind.program()
    except ImportError as e:
        raise BenchError(f"the program's sources are not in {src}: {e}") from None


def enable_cache() -> None:
    """JAX's persistent compilation cache in the checkout, at a fixed path;
    every compile is kept, so a second run compiles nothing."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileWatch:
    """Counts backend compiles and persistent-cache hits and misses."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ------------------------------------------------------------- the window --


class ProfileWindow:
    """The traced part of a ``--trace 1`` window, driven by a kind's window
    loop: the device-only profiler starts at the first tick that begins
    ``TRACE_START_S`` or more into the window and stops after the first tick
    that ends ``TRACE_LEN_S`` or more after it started (both shortened for
    short windows). ``first`` and ``end`` are then the traced tick range."""

    def __init__(self, trace_dir: str, seconds: float):
        self.dir = trace_dir
        self.at = min(TRACE_START_S, seconds / 4)
        self.length = min(TRACE_LEN_S, seconds / 2)
        self.first = self.end = self.started = None

    def before_tick(self, t0: float, n_ticks: int) -> None:
        import jax

        if self.first is None and time.perf_counter() - t0 >= self.at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.first, self.started = n_ticks, time.perf_counter()

    def after_tick(self, t_end: float, n_ticks: int) -> None:
        if self.first is not None and self.end is None and t_end - self.started >= self.length:
            self.close(n_ticks)

    def close(self, n_ticks: int) -> None:
        import jax

        if self.first is not None and self.end is None:
            self.end = n_ticks
            jax.profiler.stop_trace()


def run_cell(cfg_doc: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, peaks: dict, per_layer: list, end_metrics: list,
             program, *, log=sys.stderr) -> dict:
    """One run of a cell; returns the result object. ``program`` is what
    :func:`import_program` returned for the configuration's kind."""
    import jax

    kind = load_kind(kind_of(cfg_doc))
    watch = CompileWatch()
    cell = kind.Cell(cfg_doc, traffic, program)
    cell.start(seed)
    cell.warm_up()
    setup_s = time.perf_counter() - T_START
    compiles_before = watch.compiles
    tmp = tempfile.TemporaryDirectory(prefix="bench-trace-") if trace else None
    try:
        profile = ProfileWindow(tmp.name, seconds) if tmp else None
        win = kind.run_window(cell, seconds, profile)
        compiles_in_window = watch.compiles - compiles_before
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        # the allocator's peak plus what it holds reserved for the compiled
        # programs' temporaries, which it does not count as in use
        peak = int(stats.get("peak_bytes_in_use", 0)) + int(
            stats.get("peak_bytes_reserved", stats.get("bytes_reserved", 0)))
        e2e = kind.end_to_end(win)
        print(f"{kind.summary(win)}; compiles in window {compiles_in_window}; "
              f"peak bytes {peak}; cache hits {watch.hits} "
              f"misses {watch.misses}", file=log)
        print(f"setup_s {setup_s:.3f}; memory_stats {stats}", file=log)
        t_check = time.perf_counter()
        ok, checks = kind.verify(cell, win)  # frees the program's state first
        print(f"check took {time.perf_counter() - t_check:.3f} s", file=log)
        metrics = {}
        breakdown = None
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
        if trace:
            import trace_reduce

            red = trace_reduce.reduce_dir(tmp.name, kind.STEP_MODULE)
            ticks = win.ticks[profile.first:profile.end] if profile.first is not None else []
            ctx = kind.trace_context(cell, win, red, ticks, peaks)
            for m in per_layer:
                value = load_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = red["busy_ns"] / 1e9
            device["window_s"] = red["window_ns"] / 1e9
            breakdown = {"device_ops": red["top_ops"], "idle_gaps": red["idle_gaps"]}
        else:
            values = {**e2e, "setup_s": setup_s}
            for m in end_metrics:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    finally:
        if tmp is not None:
            tmp.cleanup()
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=log)
    result = {"correct": ok and win.failed == 0, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_json(ROOT / "BENCHMARK.json")
        cell = find_cell(spec, args.workload)
        cfg_doc = load_json(BENCH / "configs" / f"{cell['config']}.json")
        traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
        kind = load_kind(kind_of(cfg_doc))
        try:
            kind.validate(cfg_doc, traffic)
        except (KeyError, ValueError) as e:
            raise BenchError(f"cell {args.workload!r}: {e}") from None
        program = import_program(kind)
        enable_cache()
        import jax

        devices = jax.devices()
        print(f"host cpus {sorted(os.sched_getaffinity(0))}", file=sys.stderr)
        print(f"platform {devices[0].platform}  device_kind "
              f"{devices[0].device_kind}  devices {len(devices)}", file=sys.stderr)
        if devices[0].platform != "tpu":
            raise BenchError("JAX found no TPU")
        if len(devices) < cell["chips"]:
            raise BenchError(f"the cell asks for {cell['chips']} chips; JAX "
                             f"sees {len(devices)}")
        peaks = load_peaks(devices[0].device_kind)
        result = run_cell(
            cfg_doc, traffic, args.seed, args.seconds, bool(args.trace), peaks,
            cell_metrics(spec["per_layer"], args.workload) if args.trace else [],
            cell_metrics(spec["end_to_end"], args.workload), program)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
