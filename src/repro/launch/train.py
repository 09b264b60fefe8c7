"""Distributed training launcher: --arch <id> picks the architecture, the
mesh spans whatever devices exist (or the production mesh under the
dry-run env), and the Supervisor provides checkpoint/restart fault
tolerance. On CPU this runs the smoke-scale config end to end; on a real
pod the same file runs the full config — nothing here is CPU-specific.

Usage (single controller):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --steps 50 --batch 8 --seq 64 [--full-config] [--ckpt DIR]

Multi-controller (one invocation PER process, same coordinator):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --coordinator 127.0.0.1:9876 --num-processes 2 --process-id <i> ...

--batch is the GLOBAL batch; each host feeds batch/n_hosts rows striped by
the lm_data (host_id, n_hosts) contract, assembled into dim-0-sharded
global arrays, so the gradient psum over the mesh's data axis is a real
cross-host collective.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.data import lm_data
from repro.distributed import runtime
from repro.distributed.sharding import default_rules, tree_shardings_for, use_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import zoo
from repro.train import ft
from repro.train import optimizer as opt
from repro.train import trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8,
                    help="GLOBAL batch size (split across hosts)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full-size config (needs real accelerators)")
    ap.add_argument("--int8-moments", action="store_true")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 — enables multi-controller mode")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--losses-out", default=None,
                    help="host 0 writes the per-step loss series here (json)")
    args = ap.parse_args(argv)

    # must run before ANY backend touch (device queries included)
    if args.coordinator:
        ctx = runtime.initialize(coordinator_address=args.coordinator,
                                 num_processes=args.num_processes,
                                 process_id=args.process_id)
    else:
        ctx = runtime.get_context()
    enable_compile_cache()
    if args.batch % ctx.n_hosts != 0:
        raise SystemExit(
            f"--batch {args.batch} is the GLOBAL batch and must divide over "
            f"{ctx.n_hosts} hosts")
    local_batch = args.batch // ctx.n_hosts
    lead = ctx.host_id == 0

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = smoke_config(cfg)
    api = zoo.get_api(cfg)
    n_dev = jax.device_count()
    mesh = make_host_mesh(n_data=n_dev, n_model=1, ctx=ctx)
    rules = default_rules(mesh, fsdp=cfg.fsdp)
    batch_sharding = NamedSharding(mesh, P("data"))

    ocfg = opt.AdamWConfig(total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
                           int8_moments=args.int8_moments)
    step_fn_raw = trainer.make_train_step(
        api.loss_fn, ocfg, n_microbatch=args.microbatch,
        batch_sharding=batch_sharding if ctx.is_multi_controller else None)

    def init_state():
        params = api.init_params(jax.random.PRNGKey(0))
        return trainer.init_train_state(params, ocfg)

    def template():
        return jax.eval_shape(init_state)

    with mesh, use_rules(rules):
        state_sh = tree_shardings_for(
            mesh, trainer.train_state_axes(api.param_axes(), ocfg),
            jax.eval_shape(init_state), rules,
        )
        step = jax.jit(step_fn_raw, in_shardings=(state_sh, None),
                       out_shardings=(state_sh, None), donate_argnums=(0,))
        # jit the init on BOTH paths: multi-controller needs GLOBAL arrays
        # with the training shardings (eager init leaves host-local arrays
        # the step jit cannot consume), and the jit's fresh output buffers
        # also keep donate_argnums sound — eager init can alias two state
        # leaves to one buffer, which Execute() rejects as a double donation
        make_state = jax.jit(init_state, out_shardings=state_sh)

        losses = []

        def run_step(state, t):
            local = lm_data.batch_at(t, batch_size=local_batch, seq_len=args.seq,
                                     vocab=cfg.vocab_size,
                                     host_id=ctx.host_id, n_hosts=ctx.n_hosts)
            batch = ctx.global_batch(local, batch_sharding)
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
            if lead and t % 10 == 0:
                print(f"step {t:5d} loss {losses[-1]:.4f} lr {float(m['lr']):.2e} "
                      f"gnorm {float(m['grad_norm']):.3f}")
            return state

        t0 = time.time()
        if args.ckpt:
            hb = (f"{args.ckpt}/hb_host{ctx.host_id}.json"
                  if ctx.is_multi_controller else args.ckpt + "/hb.json")
            sup = ft.Supervisor(ckpt_root=args.ckpt, save_every=20,
                                heartbeat=ft.Heartbeat(hb), ctx=ctx)
            state = sup.run(init_state=make_state, state_template=template,
                            step_fn=run_step, n_steps=args.steps,
                            shardings=state_sh if ctx.is_multi_controller else None)
        else:
            state = make_state()
            for t in range(args.steps):
                state = run_step(state, t)
        dt = time.time() - t0
        toks = args.steps * args.batch * args.seq
        if lead:
            print(f"{args.arch}: {args.steps} steps, loss {losses[0]:.3f} -> "
                  f"{losses[-1]:.3f}, {toks/dt:.0f} tok/s")
        if args.losses_out and lead:
            with open(args.losses_out, "w") as f:
                json.dump(losses, f)
        if losses[-1] >= losses[0]:
            raise SystemExit("loss did not decrease")


if __name__ == "__main__":
    main()
