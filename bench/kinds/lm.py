"""The LM kind: closed-loop token serving through the program's
``LMEngineCore`` and ``Engine``.

The configuration names the program's architecture (``arch_id``, resolved
by ``repro.configs.get_config``) and its plain reference (``reference``:
``bench/lm_refs/<name>.py``, found by name). Its ``model`` group holds the
published config; the program's config must agree with it, except for the
keys listed in ``reduced`` (each with its published value), which are set
from ``model``. The reference module provides ``PUBLISHED_KEYS``,
``program_config(model)``, ``make_weights(model, seed, init)``,
``to_program(weights)``, ``logits(weights, model, tokens, rows)`` and
``decode_work(model, dtype_bytes, slots, live_positions)``.

Set-up makes the weights from the configuration's weight seed in the
reference's layout and converts them to the program's (the reference
module's ``to_program``), builds ``LMEngineCore`` and ``Engine`` at the
traffic's slot count and ``max_seq``, and serves one 2-token request of
every prompt length of the mix (``lm_traffic``): that compiles or loads the
decode step, every prefill bucket, and the admission's per-length copies
into the shared cache. The window serves the closed loop with greedy
decoding and no end-of-sequence token (``eos_id`` −1), so output lengths are
those drawn. Latencies are on the host clock, observed once per tick: a
request's first token counts toward time to first token (from its
submission), every later one toward the inter-token latency (from the
arrival of the token before it).

After the window, once the engine's cache is freed, ``check_requests``
finished requests are drawn from the seed, the one with the most served
tokens among them. Their prompts are admitted again through the program's
own ``admit`` and decoded through its own ``step`` at the cell's slot count
and ``max_seq``, teacher-forced with the served tokens, and the program's
logits at every served position are kept. The reference (float32,
``highest`` precision) then runs once over each prompt with its served
tokens. Compared, in units of the reference logits' standard deviation at
each position:

* ``token_gap``: the widest gap by which a served token's reference logit
  lies below the reference's largest (what the timed path itself served);
* ``token_miss``: the served tokens whose gap exceeds ``token_gap``'s limit;
* ``logit_gap``: the largest |program − reference| logit.
"""
from __future__ import annotations

import gc
import importlib.util
import time
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import lm_traffic

# the decode step's module in the device trace (``jax.jit(api.decode_fn)``)
STEP_MODULE = "jit_decode"
REFS = Path(__file__).resolve().parents[1] / "lm_refs"
LIMITS = ("token_gap", "logit_gap")
_REFS: dict = {}


def program():
    """The parts of the program this kind drives."""
    from repro.configs import get_config
    from repro.serve import engine

    return get_config, engine


def load_ref(name: str):
    """The reference module ``bench/lm_refs/<name>.py``."""
    if name not in _REFS:
        path = REFS / f"{name}.py"
        if not path.is_file():
            raise ValueError(f"no reference {path}")
        spec = importlib.util.spec_from_file_location(f"bench_lm_ref_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _REFS[name] = mod
    return _REFS[name]


def validate(cfg_doc: dict, traffic: dict) -> None:
    """Raise ValueError where the configuration or the traffic is not one
    this kind can run."""
    for key in ("arch_id", "reference", "model", "reduced", "weights", "limits"):
        if key not in cfg_doc:
            raise ValueError(f"configuration {cfg_doc.get('name')!r} has no {key!r}")
    if set(cfg_doc["limits"]) != set(LIMITS):
        raise ValueError(f"limits {sorted(cfg_doc['limits'])}: want {', '.join(LIMITS)}")
    load_ref(cfg_doc["reference"])
    if not traffic["slots"] >= traffic["clients"] >= 1:
        raise ValueError("traffic needs 1 <= clients <= slots")
    if traffic["total_max"] >= traffic["max_seq"]:
        raise ValueError("a request's prompt and output must fit below max_seq")
    if not 1 <= traffic["check_requests"] <= traffic["slots"]:
        raise ValueError("check_requests must be between 1 and the slot count")


def program_config(cfg_doc: dict, get_config):
    """The program's config for ``arch_id``, with the keys of ``reduced``
    set from the configuration's ``model``; any other departure from the
    published values is an error."""
    ref = load_ref(cfg_doc["reference"])
    cfg = get_config(cfg_doc["arch_id"])
    want = ref.program_config(cfg_doc["model"])
    head_dim = want.pop("head_dim")
    over = {}
    for field, value in want.items():
        if getattr(cfg, field) != value:
            key = ref.PUBLISHED_KEYS.get(field, field)
            if key not in cfg_doc["reduced"]:
                raise ValueError(f"{cfg_doc['arch_id']}: the program's {field} is "
                                 f"{getattr(cfg, field)!r}, the configuration's "
                                 f"{key} {value!r}, and {key} is not in reduced")
            over[field] = value
    return replace(cfg, head_dim=head_dim, **over)


class Cell:
    """Everything set-up builds for one run of a cell."""

    def __init__(self, cfg_doc: dict, traffic: dict, program):
        get_config, engine = program
        self.cfg_doc, self.traffic = cfg_doc, traffic
        self.model = cfg_doc["model"]
        self.ref = load_ref(cfg_doc["reference"])
        self.cfg = program_config(cfg_doc, get_config)
        weights = self.ref.make_weights(self.model, cfg_doc["weights"]["seed"],
                                        cfg_doc["weights"], self.cfg.param_dtype)
        self.params = jax.block_until_ready(self.ref.to_program(weights))
        del weights
        self.lengths = lm_traffic.lengths(traffic)
        self._engine = engine
        self.core = self.engine = None

    def start(self, seed: int) -> None:
        """A fresh engine at the traffic's slot count and ``max_seq``, and
        the clients of ``seed``."""
        t = self.traffic
        self.seed = seed
        self.core = self._engine.LMEngineCore(self.cfg, self.params, n_slots=t["slots"],
                                              max_seq=t["max_seq"])
        self.engine = self._engine.Engine(core=self.core)
        self.clients = [lm_traffic.Client(t, self.cfg.vocab_size, seed, i)
                        for i in range(t["clients"])]
        self._rid = 0

    def stop(self) -> None:
        """Drop the engine and the program's state."""
        self.core = self.engine = None
        gc.collect()

    def submit(self, prompt: list, new_tokens: int):
        req = self._engine.Request(rid=self._rid, prompt=prompt,
                                   max_new_tokens=new_tokens, eos_id=-1)
        self._rid += 1
        res = self.engine.submit(req)
        if not res:
            raise RuntimeError(f"request refused by the engine: {res.reason}")
        return req

    def warm_up(self) -> None:
        """Serve one 2-token request of every prompt length of the mix."""
        for plen in sorted({p for p, _ in self.lengths}):
            self.submit([0] * plen, 2)
        result = self.engine.run()
        if not result.drained:
            raise RuntimeError("warm-up did not drain")
        self.engine.finished.clear()


class Window:
    """The measured window: per-token latencies and per-tick records."""

    def __init__(self):
        self.ttft: list[float] = []
        self.itl: list[float] = []
        # (t_start, t_end, sequences decoded, live positions they attend to)
        self.ticks: list[tuple] = []
        self.requests: list = []  # every request submitted in the window
        self.tokens = 0
        self.elapsed = 0.0
        self.failed = 0

    @property
    def attempted(self) -> int:
        return len(self.requests)


def run_window(cell: Cell, seconds: float, profile=None) -> Window:
    """Serve for ``seconds``; ``profile`` (the harness's profiler window)
    traces part of it."""
    win = Window()
    # per client: [request, submitted at, last token at, tokens seen], or None
    live: list = [None] * len(cell.clients)
    joined = [False] * len(cell.clients)

    def submit(i: int) -> None:
        req = cell.submit(*cell.clients[i].next())
        win.requests.append(req)
        live[i] = [req, time.perf_counter(), 0.0, 0]

    t0 = time.perf_counter()
    while True:
        for i, client in enumerate(cell.clients):
            if not joined[i] and time.perf_counter() - t0 >= client.join_s:
                joined[i] = True
                submit(i)
        if profile is not None:
            profile.before_tick(t0, len(win.ticks))
        ta = time.perf_counter()
        cell.engine.run(max_steps=1)
        tb = time.perf_counter()
        decoded = positions = 0
        for st in live:
            if st is None:
                continue
            req, n = st[0], len(st[0].out)
            if n == st[3]:
                continue
            decoded += 1
            positions += len(req.prompt) + n - 1
            for k in range(st[3], n):
                if k == 0:
                    win.ttft.append(tb - st[1])
                else:
                    win.itl.append(tb - st[2])
                st[2] = tb
            win.tokens += n - st[3]
            st[3] = n
        win.ticks.append((ta, tb, decoded, positions))
        if tb - t0 >= seconds:
            break
        for i, st in enumerate(live):
            if st is not None and st[0].done:
                submit(i)
        if profile is not None:
            profile.after_tick(tb, len(win.ticks))
    if profile is not None:
        profile.close(len(win.ticks))
    win.elapsed = win.ticks[-1][1] - t0
    win.failed = len(cell.engine.rejected)
    return win


def end_to_end(win: Window) -> dict:
    return {
        "tokens_per_s": win.tokens / win.elapsed,
        "ttft_p50_ms": float(np.percentile(np.asarray(win.ttft, np.float64), 50) * 1e3),
        "itl_p50_ms": float(np.percentile(np.asarray(win.itl, np.float64), 50) * 1e3),
    }


def summary(win: Window) -> str:
    """One line on the window for standard error."""
    ticks_ms = [1e3 * (t[1] - t[0]) for t in win.ticks if t[2]]
    done = sum(1 for r in win.requests if r.done)
    ttft = np.percentile(np.asarray(win.ttft) * 1e3, [50, 90, 95, 100])
    itl = np.percentile(np.asarray(win.itl) * 1e3, [50, 90, 95, 99])
    return (f"window: {len(win.ticks)} ticks, {win.tokens} tokens, {len(win.ttft)} "
            f"first tokens, {done} requests finished, {win.elapsed:.3f} s; tick "
            f"median {np.median(ticks_ms):.3f} ms; ttft p50/p90/p95/max "
            f"{'/'.join(f'{v:.2f}' for v in ttft)} ms; itl p50/p90/p95/p99 "
            f"{'/'.join(f'{v:.2f}' for v in itl)} ms")


def sample_requests(requests: list, seed: int, n: int) -> list:
    """Up to ``n`` finished requests drawn from the seed, the one with the
    most served tokens among them."""
    done = sorted((r for r in requests if r.done), key=lambda r: (-len(r.out), r.rid))
    if not done:
        return []
    rest = [done[k] for k in lm_traffic.seed_rng(seed, 3).permutation(len(done) - 1) + 1]
    return [done[0]] + rest[:n - 1]


def replay(cell: Cell, requests: list) -> list:
    """The program's logits (float32, one row per served token) for each
    request, from its own admission and decode step over a fresh cache,
    teacher-forced with the served tokens. Slot i holds request i."""
    core = cell.core
    core.cache = core.api.init_cache(core.n_slots, core.max_seq)
    seen: list = []
    prefill_fn, decode = core._prefill_fn, core._decode

    def keep_prefill(length):
        fn = prefill_fn(length)

        def run(*args, **kw):
            out = fn(*args, **kw)
            seen.append(out[0])
            return out
        return run

    def keep_decode(*args):
        out = decode(*args)
        seen.append(out[0])
        return out

    rows = []
    core._prefill_fn, core._decode = keep_prefill, keep_decode
    try:
        forced = []
        for i, req in enumerate(requests):
            r = cell._engine.Request(rid=req.rid, prompt=req.prompt,
                                     max_new_tokens=core.max_seq, eos_id=-1)
            core.admit(r, i)
            rows.append([np.asarray(jax.device_get(seen.pop()))[0]])
            forced.append(r)
        for t in range(max(len(req.out) for req in requests) - 1):
            active = {}
            for i, req in enumerate(requests):
                if t < len(req.out) - 1:
                    forced[i].out = [req.out[t]]
                    active[i] = forced[i]
            core.step(active)
            got = np.asarray(jax.device_get(seen.pop()))
            for i in active:
                rows[i].append(got[i])
    finally:
        core._prefill_fn, core._decode = prefill_fn, decode
    return [np.stack(r) for r in rows]


def _bucket(n: int, least: int) -> int:
    b = least
    while b < n:
        b *= 2
    return b


@jax.jit
def row_stats(ref_rows, other_rows, tokens):
    """Per row, in units of the reference row's standard deviation: how far
    ``tokens``' reference logit lies below the reference's largest, and the
    largest |other − reference|."""
    std = jnp.std(ref_rows, axis=-1)
    top = jnp.max(ref_rows, axis=-1)
    tok = jnp.take_along_axis(ref_rows, tokens[:, None], axis=-1)[:, 0]
    gap = jnp.max(jnp.abs(other_rows - ref_rows), axis=-1)
    return (top - tok) / std, gap / std


def reference_rows(cell: Cell, weights, req):
    """The reference's float32 logits (on the device) at the positions where
    ``req``'s tokens were served, padded to a power-of-two row count."""
    n, plen = len(req.out), len(req.prompt)
    seq = np.asarray(req.prompt + req.out[:-1], np.int32)
    tokens = np.zeros(_bucket(len(seq), 64), np.int32)
    tokens[:len(seq)] = seq
    rows = np.zeros(_bucket(n, 16), np.int32)
    rows[:n] = np.arange(plen - 1, plen - 1 + n)
    return cell.ref.logits(weights, cell.model, jnp.asarray(tokens), jnp.asarray(rows))


def compare(cell: Cell, requests: list, program_rows: list, weights) -> dict:
    """``token_gap`` and ``logit_gap`` (and ``token_miss`` against
    ``token_gap``'s limit) of the program's rows against the reference."""
    tok_gaps, logit_gaps = [], []
    for req, prog in zip(requests, program_rows):
        ref_rows = reference_rows(cell, weights, req)
        n = len(req.out)
        pad = np.zeros((ref_rows.shape[0], ref_rows.shape[1]), np.float32)
        pad[:n] = prog
        toks = np.zeros(ref_rows.shape[0], np.int32)
        toks[:n] = req.out
        g, d = jax.device_get(row_stats(ref_rows, jnp.asarray(pad), jnp.asarray(toks)))
        tok_gaps.append(g[:n])
        logit_gaps.append(d[:n])
    tok_gaps = np.concatenate(tok_gaps)
    return {"token_gap": float(tok_gaps.max()),
            "logit_gap": float(np.concatenate(logit_gaps).max()),
            "token_miss": int((tok_gaps > cell.cfg_doc["limits"]["token_gap"]).sum()),
            "tokens": int(tok_gaps.size)}


def control_weights(weights: dict, matrices) -> dict:
    """The weights with every matrix named in ``matrices`` (the embedding
    among them) rounded to e4m3, an 8-bit float, below the configuration's
    bfloat16: each matrix with its own scale, which maps its largest
    magnitude to e4m3's largest. Norm gains and biases stay as they are.

    The e4m3 values leave the first program as e4m3 arrays and are widened
    (to float32, times the scale) in a second one: on the TPU a round trip
    f32 → e4m3 → f32 inside one program ran at the higher precision and
    changed nothing."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)

    @jax.jit
    def narrow(w):
        q, scales = {}, {}
        for name in matrices:
            if name in w:
                a = w[name].astype(jnp.float32)
                scales[name] = jnp.max(jnp.abs(a), axis=(-2, -1), keepdims=True) / top
                q[name] = (a / scales[name]).astype(jnp.float8_e4m3fn)
        return q, scales

    @jax.jit
    def widen(w, q, scales):
        return w | {name: q[name].astype(jnp.float32) * scales[name] for name in q}

    return widen(weights, *narrow(weights))


def control(cell: Cell, requests: list, weights) -> dict:
    """The control's ``token_gap`` and ``logit_gap``: the reference with its
    matrices in e4m3 (``control_weights``) in the program's place, at each
    position where ``requests``' tokens were served, with the token that the
    control puts first there."""
    low = control_weights(weights, cell.ref.MATRICES)
    tok_gaps, logit_gaps = [], []
    for req in requests:
        ref_rows = reference_rows(cell, weights, req)
        ctl_rows = reference_rows(cell, low, req)
        g, d = jax.device_get(row_stats(ref_rows, ctl_rows, jnp.argmax(ctl_rows, axis=-1)))
        tok_gaps.append(g[:len(req.out)])
        logit_gaps.append(d[:len(req.out)])
    return {"token_gap": float(np.concatenate(tok_gaps).max()),
            "logit_gap": float(np.concatenate(logit_gaps).max())}


def replay_sample(cell: Cell, win: Window) -> tuple[list, list, dict | None]:
    """Free the engine's cache, draw the seed's sample of the window's
    finished requests and replay it through the program (``replay``), free
    the program's state, then make the reference's weights: (sampled
    requests, program rows, weights; None where nothing finished)."""
    sampled = sample_requests(win.requests, cell.seed, cell.traffic["check_requests"])
    cell.engine = None
    cell.core.cache = None
    gc.collect()
    prog = replay(cell, sampled) if sampled else []
    cell.stop()  # free the program's state before the reference runs
    if not sampled:
        return sampled, prog, None
    w = cell.cfg_doc["weights"]
    return sampled, prog, cell.ref.make_weights(cell.model, w["seed"], w, cell.cfg.param_dtype)


def verify(cell: Cell, win: Window) -> tuple[bool, dict]:
    """Replay a sample of the window's finished requests through the
    program and compare it with the reference: (ok, each compared number
    beside its limit)."""
    sampled, prog, weights = replay_sample(cell, win)
    limits = cell.cfg_doc["limits"]
    if sampled:
        found = compare(cell, sampled, prog, weights)
    else:  # no finished request to check: every number reads as failing
        found = {k: None for k in (*LIMITS, "token_miss")}
    checks = {k: {"value": found[k], "limit": limits[k]} for k in LIMITS}
    checks["token_miss"] = {"value": found["token_miss"], "limit": 0}
    checks["requests_checked"] = {"value": len(sampled), "limit": 1}
    ok = bool(sampled) and all(found[k] <= limits[k] for k in LIMITS) \
        and found["token_miss"] == 0
    return ok, checks


def trace_context(cell: Cell, win: Window, red: dict, ticks: list, peaks: dict) -> dict:
    """What the per-layer readers of this kind read: the trace reduction,
    the traced ticks, and the work of the mean decode step among them."""
    decoded = [t for t in ticks if t[2]]
    work = {}
    if decoded:
        size = np.dtype(cell.cfg.param_dtype).itemsize
        each = [cell.ref.decode_work(cell.model, size, t[2], t[3]) for t in decoded]
        flops = sum(w["flops"] for w in each) / len(each)
        nbytes = sum(w["bytes"] for w in each) / len(each)
        work = {"flops_per_step": flops, "bytes_per_step": nbytes,
                "roofline_s": max(flops / peaks["bf16_flops_per_s"],
                                  nbytes / peaks["hbm_bytes_per_s"])}
    return {"trace": red, "ticks": ticks, "work": work, "peaks": peaks}
