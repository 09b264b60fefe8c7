"""CPU tests of the chip benchmark: what it finds by name, the trace
reduction, the work counter, the clips, the result line, its refusal
without a TPU, and that its comparison fails the control and each fault a
serving cell can have.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The cells run here at a smoke size (24×32 frames, 6×8 blocks), where the
Pallas kernels run in interpret mode, and are held to the configurations'
own limits. (On the CPU the program and the reference differ by an ulp
where XLA contracts a multiply-add into one rounding in one program and not
in the other, far inside the ``head_gap`` limit.)
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import frames  # noqa: E402
import reference as ref  # noqa: E402
import control  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
import work  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
DET = run.load_kind("detector")
PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
SMOKE_MODEL = dict(input_hw=[24, 32], stem_channels=8, conv_block_channels=8,
                   stage_channels=[[8, 8], [8, 8], [8, 16], [16, 16], [16, 16]],
                   pooled_stages=1, block_hw=[6, 8])


def smoke_cell(config: str, traffic_name: str = "fleet8"):
    """(config doc, traffic doc) at the smoke size."""
    doc = run.load_json(BENCH / "configs" / f"{config}.json")
    doc["model"].update(SMOKE_MODEL)
    traffic = run.load_json(BENCH / "traffic" / f"{traffic_name}.json")
    traffic.update(clip_frames=9, first_clip_stagger=min(1, traffic["first_clip_stagger"]),
                   pool_frames=12)
    return doc, traffic


def run_smoke(workload: str = "mixed-fleet8", *, trace: bool = False,
              seconds: float = 1.0, seed: int = 2**31 + 11) -> dict:
    cell = run.find_cell(SPEC, workload)
    doc, traffic = smoke_cell(cell["config"], cell["traffic"])
    return run.run_cell(doc, traffic, seed, seconds, trace, PEAKS,
                        run.cell_metrics(SPEC["per_layer"], workload),
                        run.cell_metrics(SPEC["end_to_end"], workload),
                        run.import_program(DET))


# ---------------------------------------------------------------- by name --


def test_cells_configs_traffic_and_readers_found_by_name():
    from repro.models import snn_yolo

    names = {c["name"] for c in SPEC["configs"]}
    for cfg in SPEC["configs"]:
        assert Path(cfg["file"]) == Path("bench/configs") / f"{cfg['name']}.json"
    for path in sorted((BENCH / "configs").glob("*.json")):
        doc = run.load_json(path)
        assert doc["name"] == path.stem
        if run.kind_of(doc) != "detector":
            continue  # the other kinds' configurations: test_lm.py
        snn_yolo.config_from_dict(doc["model"])  # every model key is the program's
        assert set(doc["limits"]) == {"head_gap", "det_mismatch"}
    for cell in SPEC["workloads"]:
        assert cell["config"] in names
        doc = run.load_json(BENCH / "configs" / f"{cell['config']}.json")
        traffic = run.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
        run.load_kind(run.kind_of(doc)).validate(doc, traffic)
    for path in sorted((BENCH / "traffic").glob("*.json")):
        traffic = run.load_json(path)
        if "streams" in traffic:  # a detector mix
            DET.validate(run.load_json(BENCH / "configs" / "snn-det-mixed.json"), traffic)
    computed = {"frames_per_s", "frame_p50_ms", "frame_p95_ms", "setup_s",
                "tokens_per_s", "ttft_p50_ms", "itl_p50_ms"}
    assert {m["name"] for m in SPEC["end_to_end"]} <= computed
    for m in SPEC["per_layer"]:
        read = run.load_reader(m["name"])
        ctx = {"trace": tr.empty(), "ticks": [], "work": {}, "peaks": PEAKS,
               "frames_per_step": 0.0}
        assert read(ctx) is None, f"{m['name']} reads a number from nothing"


def test_unknown_names_are_refused():
    with pytest.raises(run.BenchError):
        run.find_cell(SPEC, "no-such-cell")
    with pytest.raises(run.BenchError):
        run.load_reader("no_such_metric")
    with pytest.raises(run.BenchError):
        run.load_peaks("TPU v0")


# -------------------------------------------------------------- the trace --

KERNEL = '%_dispatch_fused.1 = s8[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
FUSION = "%fusion.2 = f32[8]{0} fusion(), kind=kLoop"
CONCAT = '%custom-call.3 = f32[8]{0} custom-call(), custom_call_target="ConcatBitcast"'


def hand_trace() -> tr.Events:
    ops = [("%copy.0 = f32[] copy()", 0, 150),  # starts before the window
           (KERNEL, 110, 210), (FUSION, 200, 300),  # step 1, overlapping
           (KERNEL, 600, 700), (FUSION, 750, 850), (CONCAT, 860, 870),  # step 2
           (KERNEL, 1100, 1150)]  # the last step starts the window's end
    modules = [("jit__masked(1)", 100, 400), ("jit__other(2)", 420, 440),
               ("jit__masked(1)", 600, 900), ("jit__masked(1)", 1100, 1200)]
    return tr.Events(ops, modules)


def test_trace_reduction_on_hand_built_events():
    red = tr.reduce(hand_trace())
    assert red["window_ns"] == 1000  # first step start to last step start
    # busy: [100,300] ∪ [600,700] ∪ [750,850] ∪ [860,870]; the early copy
    # is clipped to the window
    assert red["busy_ns"] == 410
    assert red["n_steps"] == 2  # jit__other is not the serving step
    assert red["step_busy_ns"] == [190, 210]  # union inside each step
    assert red["kernel_ns"] == 200  # ConcatBitcast is XLA's, not a kernel
    assert red["other_ns"] == 210
    assert red["idle_gaps"] == [[tr.BETWEEN, 300e-9], [tr.BETWEEN, 230e-9],
                                [tr.INSIDE, 50e-9], [tr.INSIDE, 10e-9]]
    assert red["top_ops"][0] == ["_dispatch_fused.1", 200e-9]
    ctx = {"trace": red, "ticks": [(0.0, 0.010, 0.008, 4), (0.010, 0.030, 0.012, 4)],
           "work": {"step_ops": 1e3, "fused_roofline_s": 1e-8}, "peaks": PEAKS,
           "frames_per_step": 4.0}
    got = {m["name"]: run.load_reader(m["name"])(ctx)
           for m in run.cell_metrics(SPEC["per_layer"], "mixed-fleet8")}
    assert got["idle_share"] == pytest.approx(59.0)
    assert got["step_device_ms"] == pytest.approx(200e-6)
    assert got["kernel_ms"] == pytest.approx(100e-6)
    assert got["xla_ops_ms"] == pytest.approx(105e-6)
    assert got["tick_host_ms"] == pytest.approx(5.0)  # mean of 2 ms and 8 ms
    # 4 frames of 1e3 ops in a mean step of 200 ns of device time
    assert got["step_mfu"] == pytest.approx(100 * 4e3 / 200e-9 / PEAKS["int8_ops_per_s"])
    assert got["kernel_roofline"] == pytest.approx(100 * 4e-8 / 100e-9)


def test_trace_without_serving_steps_reads_nothing():
    ev = hand_trace()
    assert tr.reduce(tr.Events(ev.ops, ev.modules[:2])) == tr.empty()
    assert tr.union_ns([]) == 0


# ----------------------------------------------------------- work counter --


def test_work_counter_against_hand_counts():
    doc, _ = smoke_cell("snn-det-mixed")
    net = ref.Net.from_model(doc["model"])
    names = [lay.name for lay in ref.layers(net)]
    assert len(names) == 1 + 1 + 5 * 5 + 1  # 27 fused layers and the head
    nnz = {n: 10 + i for i, n in enumerate(names)}
    w = work.count(net, nnz, PEAKS)
    # encode: 24×32, 3→8, 3×3, one step in and out, u8 pixels
    enc = w["layers"]["encode"]
    assert enc["ops"] == 2 * 10 * 24 * 32 * 1
    assert enc["bytes"] == 24 * 32 * 3 + 10 + 9 * 3 * 8 // 8 + 24 * 32 * 8 + 8 * 24 * 32 * 8
    # conv_block at 12×16, 8→8: one input step under the mixed schedule
    cb = w["layers"]["conv_block"]
    assert cb["ops"] == 2 * 11 * 12 * 16 * 1
    assert cb["bytes"] == 12 * 16 * 8 * 1 + 11 + 9 * 8 * 8 // 8 + 12 * 16 * 8 * 3 + 8 * 12 * 16 * 8
    # a stage 3×3 at 6×8 (pooled_stages=1: the stages run at 1/4 size), T=3
    ma = w["layers"]["stage0/main_a"]
    k = names.index("stage0/main_a")
    assert ma["ops"] == 2 * (10 + k) * 6 * 8 * 3
    assert "head" not in w["layers"]
    head_ops = 2 * nnz["head"] * 6 * 8 * 3
    assert w["step_ops"] == w["fused_ops"] + head_ops
    for lay in w["layers"].values():
        assert lay["roofline_s"] == max(lay["ops"] / PEAKS["int8_ops_per_s"],
                                        lay["bytes"] / PEAKS["hbm_bytes_per_s"])
    # uniform T=3: conv_block convolves 3 input steps, nothing else changes
    doc3, _ = smoke_cell("snn-det-t3")
    w3 = work.count(ref.Net.from_model(doc3["model"]), nnz, PEAKS)
    assert w3["layers"]["conv_block"]["ops"] == 3 * cb["ops"]
    assert w3["layers"]["encode"] == enc


def test_nonzeros_are_counted_from_the_quantized_weights():
    doc, _ = smoke_cell("snn-det-mixed")
    net = ref.Net.from_model(doc["model"])
    params = ref.make_params(net, 0, 0.8)
    q = ref.prepare(net, params, None, 8)
    nnz = work.nonzeros(q)
    assert set(nnz) == {lay.name for lay in ref.layers(net)}
    assert nnz["stage0/main_a"] <= np.ceil(0.2 * 9 * 8 * 8)  # 3×3 pruned 80%
    assert nnz["stage0/main_in"] > 0.9 * 8 * 8  # 1×1 kept


# ------------------------------------------------------------------ clips --


def test_clips_are_views_with_staggered_first_lengths_per_seed():
    pool = frames.render_pool(2**31 + 5, 64, (24, 32))
    assert pool.dtype == np.float32 and pool.shape == (64, 24, 32, 3)
    assert np.array_equal(np.round(pool * 255) / 255, pool)  # on the u8 grid
    assert np.array_equal(pool, frames.render_pool(2**31 + 5, 64, (24, 32)))
    srcs = [frames.ClipSource(pool, 7, i, clip_frames=58, stagger=7) for i in range(8)]
    first = [s.next_clip() for s in srcs]
    assert [len(c) for c in first] == [58 - 7 * i for i in range(8)]
    assert all(len(s.next_clip()) == 58 for s in srcs)
    for c in first:
        assert c.base is pool  # a view: building a clip copies nothing
    again = [frames.ClipSource(pool, 7, i, clip_frames=58, stagger=7).next_clip()
             for i in range(8)]
    assert all(np.shares_memory(a, b) and a.ctypes.data == b.ctypes.data
               for a, b in zip(first, again))
    other = [frames.ClipSource(pool, 8, i, clip_frames=58, stagger=7).next_clip()
             for i in range(8)]
    assert [c.ctypes.data for c in other] != [c.ctypes.data for c in first]
    with pytest.raises(ValueError):
        frames.ClipSource(pool, 7, 9, clip_frames=58, stagger=7)


def test_consecutive_frames_move():
    pool = frames.render_pool(3, 4, (96, 128), max_drift_px=4.0, noise=0.0)
    assert not np.array_equal(pool[0], pool[3])


# --------------------------------------------------------- the result line --


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_schema(trace):
    res = run_smoke("mixed-fleet8", trace=trace)
    keys = list(res)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"
    assert ("breakdown" in res) == trace
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        # the CPU trace has no TPU plane: per-layer readers of it read nothing
        assert set(res["metrics"]) <= {"tick_host_ms"}
    else:
        want = run.cell_metrics(SPEC["end_to_end"], "mixed-fleet8")
        assert set(res["metrics"]) == {m["name"] for m in want}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


def _run_cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]["name"],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert "sources" in proc.stderr
    assert not proc.stdout.strip()


# ----------------------------------------------- control and faults fail --


def test_control_fails_the_comparison():
    """The reference at FXP4 in the program's place (the control) fails a
    limit that the sound run meets."""
    doc, traffic = smoke_cell("snn-det-mixed")
    cell = DET.Cell(doc, traffic, run.import_program(DET))
    cell.start(2**31 + 3)
    cell.warm_up()
    win = DET.run_window(cell, 1.0)
    clips = DET.sample_clips(win.clips, cell.seed, traffic["check_clips"])
    cell.stop()
    expected = DET.replay(cell, clips, cell.ref_weights)
    sound = DET.check(cell, clips, expected)
    ctl = ref.compare(DET.replay(cell, clips, control.control_weights(cell)), expected)
    limits = doc["limits"]
    assert sound["frames"] == ctl["frames"] > 0
    assert all(sound[k] <= limits[k] for k in limits)
    assert ctl["head_gap"] > 100 * limits["head_gap"]
    assert ctl["det_mismatch"] > limits["det_mismatch"]


def test_control_is_one_precision_below_the_configuration():
    for cfg in SPEC["configs"]:
        doc = run.load_json(ROOT / cfg["file"])
        if run.kind_of(doc) != "detector":
            continue  # the LM kind's e4m3 control: test_lm.py
        assert doc["model"]["weight_bits"] == 8
        assert control.control_bits(doc) == 4


def _dets(rows):
    """Detections of one frame, (boxes, scores, classes, valid), from
    (cx, cy, w, h, score, class) rows and 2 empty slots."""
    rows = list(rows) + [(0, 0, 0, 0, 0, 0)] * 2
    a = np.asarray(rows, np.float32)
    valid = np.arange(len(rows)) < len(rows) - 2
    return a[:, :4], a[:, 4], a[:, 5].astype(np.int32), valid


def test_detections_match_within_tolerance_in_any_order():
    want = _dets([(0.5, 0.5, 0.1, 0.2, 0.9, 1), (0.2, 0.3, 0.05, 0.05, 0.4, 0)])
    assert ref.unmatched(want, want) == 0
    # swapped slots, and a box and score moved well inside DET_TOL
    near = _dets([(0.2, 0.3, 0.05 + 1e-4, 0.05, 0.4 - 1e-4, 0), (0.5, 0.5, 0.1, 0.2, 0.9, 1)])
    assert ref.unmatched(near, want) == 0
    # one grid cell (1/32) to the right: the pair no longer matches
    moved = _dets([(0.5 + 1 / 32, 0.5, 0.1, 0.2, 0.9, 1), (0.2, 0.3, 0.05, 0.05, 0.4, 0)])
    assert ref.unmatched(moved, want) == 2
    relabelled = _dets([(0.5, 0.5, 0.1, 0.2, 0.9, 2), (0.2, 0.3, 0.05, 0.05, 0.4, 0)])
    assert ref.unmatched(relabelled, want) == 2
    dropped = _dets([(0.5, 0.5, 0.1, 0.2, 0.9, 1)])
    assert ref.unmatched(dropped, want) == 1


def _fault(kind):
    """A broken serving step: wraps ``CompiledDetector.masked_step``."""
    import jax.numpy as jnp

    from repro.serve.detector import CompiledDetector

    orig = CompiledDetector.masked_step

    def broken(self, frames_, mem, active, cold=None):
        head, new_mem, dets = orig(self, frames_, mem, active, cold)
        if kind == "state_unchanged":  # the step returns its state unchanged
            return head, mem, dets
        if kind == "half_batch":  # half of the batch left out
            keep = jnp.arange(head.shape[0]) < head.shape[0] // 2
            head = jnp.where(keep.reshape((-1,) + (1,) * (head.ndim - 1)), head, 0.0)
            return head, new_mem, dets
        return head.at[:, 0, 0, 0, 0].add(0.5), new_mem, dets  # answer altered

    return broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "answer_altered"])
def test_faults_make_correct_false(kind, monkeypatch):
    from repro.serve.detector import CompiledDetector

    monkeypatch.setattr(CompiledDetector, "masked_step", _fault(kind))
    doc, traffic = smoke_cell("snn-det-mixed")
    # check 32 clips, so that a fault in half of the rows cannot hide
    # behind the sample (2^-32)
    traffic["check_clips"] = 32
    res = run.run_cell(doc, traffic, 2**31 + 11, 1.5, False, PEAKS, [],
                       run.cell_metrics(SPEC["end_to_end"], "mixed-fleet8"),
                       run.import_program(DET))
    assert res["correct"] is False
    assert res["checks"]["head_gap"]["value"] > res["checks"]["head_gap"]["limit"]
