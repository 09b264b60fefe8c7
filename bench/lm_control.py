"""Readings that set the limits of an LM cell's ``correct``, on the chip.

    python bench/lm_control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process (set-up once): a run of the cell as the
benchmark makes it (a window of ``--seconds``, then the sample of finished
requests replayed through the program and compared with the reference),
which gives the program's readings; and the control on the same requests:
the reference with its matrices in e4m3, an 8-bit float below the
configuration's bfloat16 (``kinds/lm.control_weights``), in the program's
place, read at each served position with the token it puts first. One JSON
line per seed, then a summary with the largest program reading and the
smallest control reading of each compared number. The benchmark's own runs
do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import run

lm = run.load_kind("lm")


def readings(cell_name: str, seeds: list, seconds: float, program, *,
             out=sys.stdout) -> dict:
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    cell_spec = run.find_cell(spec, cell_name)
    cfg_doc = run.load_json(run.BENCH / "configs" / f"{cell_spec['config']}.json")
    traffic = run.load_json(run.BENCH / "traffic" / f"{cell_spec['traffic']}.json")
    cell = lm.Cell(cfg_doc, traffic, program)
    worst = {k: 0.0 for k in lm.LIMITS}
    least = {k: float("inf") for k in lm.LIMITS}
    for seed in seeds:
        cell.start(seed)
        cell.warm_up()
        win = lm.run_window(cell, seconds)
        sampled, prog, weights = lm.replay_sample(cell, win)
        got = lm.compare(cell, sampled, prog, weights)
        ctl = lm.control(cell, sampled, weights)
        del weights, prog
        for k in worst:
            worst[k] = max(worst[k], got[k])
            least[k] = min(least[k], ctl[k])
        print(json.dumps({"seed": seed, "tokens_per_s": win.tokens / win.elapsed,
                          "requests": len(sampled), "program": got, "control": ctl}),
              file=out, flush=True)
    summary = {"workload": cell_name, "seeds": len(seeds), "control": "e4m3",
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
        program = run.import_program(lm)
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
