"""Readings that set the limits of ``correct``, on the chip.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process (set-up once): a run of the cell as the
benchmark makes it (a window of ``--seconds``, then the sample of finished
clips replayed through the reference), which gives the program's readings;
and the control on the same clips: the reference with its weights at the
next lower precision than the configuration states (FXP4 for FXP8,
:func:`control_bits`) put in the program's place. One JSON line per seed,
then a summary with the largest program reading and the smallest control
reading of each compared number. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import reference as ref
import run


def control_bits(cfg_doc: dict) -> int:
    """The control's weight precision: the next integer width below the
    configuration's (int8 → int4)."""
    return cfg_doc["model"]["weight_bits"] // 2


def control_weights(cell: run.Cell) -> dict:
    """The reference's weights at the control's precision."""
    return ref.prepare(cell.net, cell.params, cell.bn, control_bits(cell.cfg_doc))


def readings(cell_name: str, seeds: list, seconds: float, program, *,
             out=sys.stdout) -> dict:
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell_spec = run.find_cell(spec, cell_name)
    cfg_doc = run.load_json(run.BENCH / "configs" / f"{cell_spec['config']}.json")
    traffic = run.load_json(run.BENCH / "traffic" / f"{cell_spec['traffic']}.json")
    cell = run.Cell(cfg_doc, traffic, program)
    worst = {k: 0.0 for k in cfg_doc["limits"]}
    least = {k: float("inf") for k in cfg_doc["limits"]}
    lower = control_weights(cell)
    for seed in seeds:
        cell.start(seed)
        cell.warm_up()
        win, _ = run.run_window(cell, seconds)
        clips = run.sample_clips(win.clips, seed, traffic["check_clips"])
        cell.stop()
        expected = run.replay(cell, clips, cell.ref_weights)
        got = run.check(cell, clips, expected)
        # the control: the reference at the lower precision in the program's place
        ctl = ref.compare(run.replay(cell, clips, lower), expected)
        for k in worst:
            worst[k] = max(worst[k], got[k])
            least[k] = min(least[k], ctl[k])
        print(json.dumps({"seed": seed, "frames_per_s": len(win.latencies) / win.elapsed,
                          "program": got, "control": ctl}), file=out, flush=True)
    summary = {"workload": cell_name, "seeds": len(seeds), "bits": control_bits(cfg_doc),
               "program_max": worst, "control_min": least}
    print(json.dumps(summary), file=out, flush=True)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        program = run.import_program()
        run.enable_cache()
        import jax

        if jax.devices()[0].platform != "tpu":
            raise run.BenchError("JAX found no TPU")
        readings(args.workload, args.seeds, args.seconds, program)
    except run.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
