"""CPU tests of the harness's kinds and of the LM kind: the driver found by
the configuration's ``kind``, the LM window and its result line, the
reference against the program, the e4m3 control, the faults a serving cell
can have, the trace reduction by step-module name, the work counter, and a
later LM configuration plugged in by new files alone.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The LM cell runs here at a tiny Qwen-shaped size (2 layers of width 64, a
16384-token vocabulary, 4 clients in 4 slots, ``max_seq`` 128), held to the
configuration's own limits. Its weights are drawn with ``init_std`` 0.08, so
that each layer's output weighs against the embedding as the published
widths weigh with 0.02 (the gain of a matrix is ``init_std`` × √width). The
control's test keeps the published 24 layers, so that the e4m3 rounding
accumulates over as many layers as on the chip: at 2 layers its
``token_gap`` read 0.02–0.30, on both sides of the limit.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys
import uuid
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import lm_traffic  # noqa: E402
import lm_work  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
LM = run.load_kind("lm")
CELL = "qwen05b-chat16"
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, vocab_size=16384)
TINY_TRAFFIC = dict(clients=4, slots=4, max_seq=128, requests=16, join_spacing_s=0.01,
                    check_requests=4, total_max=127,
                    prompt=dict(median=24, sigma=0.5, min=4, max=80),
                    output=dict(median=16, sigma=0.5, min=2, max=40))


def tiny_lm(config: str = "qwen1.5-0.5b", **model):
    """(config doc, traffic doc) of the LM cell at the tiny size; every
    changed model key is listed in ``reduced`` with its published value."""
    doc = run.load_json(BENCH / "configs" / f"{config}.json")
    changed = {**TINY, **model}
    doc["reduced"] = {k: doc["model"].get(k) for k in changed}
    doc["model"].update(changed)
    doc["weights"]["init_std"] = 0.08
    traffic = run.load_json(BENCH / "traffic" / "chat16.json")
    traffic.update(copy.deepcopy(TINY_TRAFFIC))
    return doc, traffic


def run_lm(doc, traffic, *, trace=False, seconds=1.5, seed=2**31 + 21, spec=SPEC,
           workload=CELL):
    return run.run_cell(doc, traffic, seed, seconds, trace, PEAKS,
                        run.cell_metrics(spec["per_layer"], workload),
                        run.cell_metrics(spec["end_to_end"], workload),
                        run.import_program(LM))


def served_cell(doc, traffic, seed=2**31 + 23, seconds=1.5):
    """A cell after a window, its sampled requests replayed through the
    program, and the reference's weights."""
    cell = LM.Cell(doc, traffic, run.import_program(LM))
    cell.start(seed)
    cell.warm_up()
    win = LM.run_window(cell, seconds)
    return (cell, win, *LM.replay_sample(cell, win))


# -------------------------------------------------------------- the kinds --


def test_a_configuration_without_kind_runs_the_detector_driver():
    doc = run.load_json(BENCH / "configs" / "snn-det-mixed.json")
    assert "kind" not in doc
    assert run.kind_of(doc) == "detector"
    det = run.load_kind(run.kind_of(doc))
    assert det.STEP_MODULE == "jit__masked"
    assert run.load_kind("detector") is det  # loaded once
    lm_doc = run.load_json(BENCH / "configs" / "qwen1.5-0.5b.json")
    assert run.load_kind(run.kind_of(lm_doc)).STEP_MODULE == "jit_decode"
    for cell in SPEC["workloads"]:
        cfg = run.load_json(BENCH / "configs" / f"{cell['config']}.json")
        traffic = run.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
        run.load_kind(run.kind_of(cfg)).validate(cfg, traffic)


def test_an_unknown_kind_is_refused_with_no_result_line(tmp_path):
    spec = copy.deepcopy(SPEC)
    spec["workloads"].append({"name": "odd-cell", "config": "odd", "traffic": "fleet8",
                              "chips": 1, "why": "a kind no driver serves"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "bench" / "configs" / "odd.json").write_text(
        json.dumps({"name": "odd", "kind": "no_such_kind"}))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "odd-cell", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "kind driver" in proc.stderr
    assert not proc.stdout.strip()
    with pytest.raises(run.BenchError):
        run.load_kind("no_such_kind")


def test_the_lm_configuration_follows_the_published_sizes():
    doc = run.load_json(BENCH / "configs" / "qwen1.5-0.5b.json")
    get_config, _ = run.import_program(LM)
    cfg = LM.program_config(doc, get_config)
    assert doc["reduced"] == {}
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff,
            cfg.vocab_size) == (24, 1024, 16, 16, 64, 2816, 151936)
    assert cfg.qkv_bias and cfg.tie_embeddings and cfg.rope_theta == 1e6
    assert cfg.family == "dense" and not cfg.kv_quant and cfg.dtype == "bfloat16"
    # a size that departs from the program's without being listed is refused
    bad = copy.deepcopy(doc)
    bad["model"]["num_hidden_layers"] = 4
    with pytest.raises(ValueError, match="not in reduced"):
        LM.program_config(bad, get_config)
    bad["reduced"] = {"num_hidden_layers": 24}
    assert LM.program_config(bad, get_config).n_layers == 4


def test_the_traffic_is_the_same_set_of_lengths_for_every_seed():
    traffic = run.load_json(BENCH / "traffic" / "chat16.json")
    pairs = lm_traffic.lengths(traffic)
    assert len(pairs) == traffic["requests"]
    assert all(traffic["prompt"]["min"] <= p <= traffic["prompt"]["max"] for p, _ in pairs)
    assert all(1 <= o <= traffic["output"]["max"] and p + o <= traffic["total_max"]
               for p, o in pairs)
    assert traffic["total_max"] < traffic["max_seq"]
    served = []
    for seed in (2**31 + 1, 7):
        clients = [lm_traffic.Client(traffic, 151936, seed, i) for i in range(16)]
        served.append(sorted(pair for c in clients for pair in c.pairs))
        first = clients[3].next()
        again = lm_traffic.Client(traffic, 151936, seed, 3).next()
        assert first == again and max(first[0]) < 151936
    assert served[0] == served[1] == sorted(pairs)


# ------------------------------------------------------------- the window --


@pytest.mark.parametrize("trace", [False, True])
def test_lm_result_line_schema(trace):
    doc, traffic = tiny_lm()
    res = run_lm(doc, traffic, trace=trace)
    keys = list(res)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"
    assert ("breakdown" in res) == trace
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["checks"]) == {"token_gap", "logit_gap", "token_miss", "requests_checked"}
    assert res["checks"]["requests_checked"]["value"] == traffic["check_requests"]
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        # the CPU trace has no TPU plane: the device readers read nothing
        assert res["metrics"] == {}
    else:
        want = {m["name"] for m in run.cell_metrics(SPEC["end_to_end"], CELL)}
        assert set(res["metrics"]) == want == {"tokens_per_s", "ttft_p50_ms", "itl_p50_ms",
                                               "setup_s"}
        for m in res["metrics"].values():
            assert m["value"] > 0
    json.dumps(res)


def test_lm_window_counts_each_token_once_and_compiles_nothing():
    doc, traffic = tiny_lm()
    cell = LM.Cell(doc, traffic, run.import_program(LM))
    cell.start(2**31 + 29)
    watch = run.CompileWatch()
    cell.warm_up()
    before = watch.compiles
    win = LM.run_window(cell, 1.5)
    assert watch.compiles == before  # every shape of the window was warmed up
    started = [r for r in win.requests if r.out]
    assert len(win.ttft) == len(started) > traffic["clients"]
    assert len(win.ttft) + len(win.itl) == win.tokens == sum(len(r.out) for r in win.requests)
    assert all(r.max_new_tokens == len(r.out) for r in win.requests if r.done)
    assert max(t[2] for t in win.ticks) == traffic["slots"]
    for ta, tb, decoded, positions in win.ticks:
        assert decoded <= traffic["slots"] and positions <= decoded * traffic["max_seq"]
    e2e = LM.end_to_end(win)
    assert e2e["tokens_per_s"] == pytest.approx(win.tokens / win.elapsed)
    assert e2e["ttft_p50_ms"] > e2e["itl_p50_ms"] > 0  # a prefill precedes a first token


def test_sampled_requests_hold_the_longest_and_follow_the_seed():
    class R:
        def __init__(self, rid, n, done=True):
            self.rid, self.out, self.done = rid, [0] * n, done

    reqs = [R(i, n) for i, n in enumerate([5, 9, 3, 9, 7, 2])] + [R(6, 20, done=False)]
    a = LM.sample_requests(reqs, 11, 3)
    assert a[0].rid == 1 and len(a) == 3  # the longest finished one, lowest rid
    assert [r.rid for r in a] == [r.rid for r in LM.sample_requests(reqs, 11, 3)]
    assert all(r.done for r in a)
    assert LM.sample_requests([], 11, 3) == []


# ------------------------------------------------ reference, control, faults --


def test_reference_agrees_with_the_program_and_the_control_does_not():
    """The program's own prefill and decode, replayed teacher-forced, meet
    both limits; the reference with e4m3 matrices in its place exceeds
    ``logit_gap``'s. On the chip the control exceeds both limits, over some
    thousands of served tokens; over the few hundred here its ``token_gap``,
    the widest of the gaps where it puts another token first, read
    0.22–0.58 around the limit of 0.25, so it is held only to serving other
    tokens than the program does."""
    doc, traffic = tiny_lm(num_hidden_layers=24)
    # 8 checked requests of about 40 tokens: some hundreds of served tokens,
    # as the cell checks some thousands
    traffic.update(clients=8, slots=8, check_requests=8, requests=24,
                   prompt=dict(median=20, sigma=0.5, min=4, max=30),
                   output=dict(median=40, sigma=0.3, min=20, max=80))
    cell, win, sampled, prog, weights = served_cell(doc, traffic, seconds=10.0)
    assert len(sampled) >= 4
    limits = doc["limits"]
    got = LM.compare(cell, sampled, prog, weights)
    ctl = LM.control(cell, sampled, weights)
    assert got["tokens"] == sum(len(r.out) for r in sampled) > 100
    assert got["token_gap"] <= limits["token_gap"] and got["token_miss"] == 0
    assert got["logit_gap"] <= limits["logit_gap"]
    assert ctl["logit_gap"] > limits["logit_gap"]
    assert ctl["token_gap"] > 2 * got["token_gap"]
    # the program's first served token is its own prefill's argmax
    for req, rows in zip(sampled, prog):
        assert rows.shape == (len(req.out), doc["model"]["vocab_size"])
        assert int(np.argmax(rows[0])) == req.out[0]


def test_the_reference_runs_at_highest_precision():
    import jax
    import jax.numpy as jnp

    doc, _ = tiny_lm()
    ref = LM.load_ref(doc["reference"])
    w = ref.make_weights(doc["model"], 0, doc["weights"])
    items = tuple(sorted((k, v) for k, v in doc["model"].items()
                         if isinstance(v, (int, float, bool, str))))
    with jax.default_matmul_precision("highest"):
        text = ref._hidden.lower(w, jnp.zeros(64, jnp.int32), items).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots and all("HIGHEST" in line for line in dots)


def test_control_rounds_the_matrices_to_e4m3():
    doc, _ = tiny_lm()
    ref = LM.load_ref(doc["reference"])
    w = ref.make_weights(doc["model"], 0, doc["weights"])
    low = LM.control_weights(w, ref.MATRICES)
    assert set(low) == set(w)
    for name in w:
        a, b = np.asarray(w[name], np.float32), np.asarray(low[name], np.float32)
        if name in ref.MATRICES:
            # 3 mantissa bits against bfloat16's 7: each matrix's elements
            # move by up to 2^-4 of its largest one, the largest stays
            err = np.abs(a - b).max(axis=(-2, -1)) / np.abs(a).max(axis=(-2, -1))
            assert np.all((err > 2**-9) & (err <= 2**-4))
            assert np.allclose(np.abs(b).max(axis=(-2, -1)), np.abs(a).max(axis=(-2, -1)),
                               rtol=2**-7)
        else:
            assert np.array_equal(a, b)


def _decode_fault(kind):
    """A broken decode step: wraps the core's jitted ``_decode``."""
    from repro.serve.engine import LMEngineCore

    orig = LMEngineCore.__init__

    def init(self, *args, **kw):
        orig(self, *args, **kw)
        decode = self._decode

        def broken(params, cache, tok, pos):
            logits, new_cache = decode(params, cache, tok, pos)
            if kind == "state_unchanged":  # the step returns its cache unchanged
                return logits, cache
            if kind == "half_batch":  # the second half of the slots left out
                half = logits.shape[0] // 2
                return logits.at[half:].set(logits[0]), new_cache
            return logits.at[:, 7].add(1e3), new_cache  # token 7 wins every step

        self._decode = broken

    return init


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "token_altered"])
def test_decode_faults_make_correct_false(kind, monkeypatch):
    from repro.serve.engine import LMEngineCore

    monkeypatch.setattr(LMEngineCore, "__init__", _decode_fault(kind))
    doc, traffic = tiny_lm()
    res = run_lm(doc, traffic, seed=2**31 + 31)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_a_layer_skipped_in_the_programs_weights_makes_correct_false(monkeypatch):
    doc, traffic = tiny_lm()
    ref = LM.load_ref(doc["reference"])
    convert = ref.to_program

    def skip_layer_one(w):
        p = convert(w)
        layers = p["layers"]
        layers["attn"]["wo"] = layers["attn"]["wo"].at[1].set(0)
        layers["mlp"]["wo"] = layers["mlp"]["wo"].at[1].set(0)
        return p

    monkeypatch.setattr(ref, "to_program", skip_layer_one)
    res = run_lm(doc, traffic, seed=2**31 + 37)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


# ---------------------------------------------------- trace, work, readers --


def test_trace_reduction_takes_the_step_module_by_name():
    ops = [("%fusion.1 = f32[] fusion()", 100, 180), ("%dot.2 = f32[] dot()", 250, 300),
           ("%copy.3 = f32[] copy()", 600, 700), ("%fusion.4 = f32[] fusion()", 900, 950)]
    modules = [("jit_decode(7)", 100, 200), ("jit_prefill(3)", 240, 320),
               ("jit_decode(7)", 600, 720), ("jit_decode(7)", 900, 1000)]
    ev = tr.Events(ops, modules)
    red = tr.reduce(ev, "jit_decode")
    assert red["n_steps"] == 2 and red["window_ns"] == 800
    assert red["step_busy_ns"] == [80, 100]  # the prefill is not a decode step
    assert red["busy_ns"] == 80 + 50 + 100
    assert tr.reduce(ev) == tr.empty()  # the detector's module is not there
    ctx = {"trace": red, "ticks": [], "peaks": PEAKS,
           "work": {"flops_per_step": 2e6, "bytes_per_step": 1e3, "roofline_s": 45e-9}}
    got = {m["name"]: run.load_reader(m["name"])(ctx)
           for m in run.cell_metrics(SPEC["per_layer"], CELL)}
    assert got["decode_step_ms"] == pytest.approx(90e-6)
    assert got["lm_step_mfu"] == pytest.approx(100 * 2e6 / 90e-9 / PEAKS["bf16_flops_per_s"])
    assert got["decode_roofline"] == pytest.approx(50.0)
    assert got["idle_share.lm"] == pytest.approx(100 * (1 - 230 / 800))
    empty = {"trace": tr.empty(), "ticks": [], "work": {}, "peaks": PEAKS}
    assert all(v is None for v in (run.load_reader(n)(empty) for n in got))


def test_lm_work_counter_against_hand_counts():
    doc = run.load_json(BENCH / "configs" / "qwen1.5-0.5b.json")
    ref = LM.load_ref(doc["reference"])
    s = ref.shapes(doc["model"])
    body, total = lm_work.dense_params(s)
    per_layer = 4 * 1024 * 1024 + 3 * 1024 + 3 * 1024 * 2816
    assert body == 24 * per_layer
    assert total == body + 24 * 2 * 1024 + 1024 + 151936 * 1024
    assert total == 463_987_712  # Qwen1.5-0.5B's published count, tied head
    w = ref.decode_work(doc["model"], 2, 16, 10_000)
    assert w["flops"] == 2 * body * 16 + 2 * 1024 * 151936 * 16 + 4 * 24 * 16 * 64 * 10_000
    assert w["bytes"] == 2 * total + 2 * 24 * 16 * 64 * 2 * 10_000


# ----------------------------------------------- a later configuration --

PLUG_REF = '''"""A second dense-decoder reference, for the plug-in test: Qwen2 with
grouped k/v heads, its matrices (out, in) as published checkpoints lay them
out, and its own converter."""
import jax
import jax.numpy as jnp

import lm_work

PUBLISHED_KEYS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
                  "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
                  "d_ff": "intermediate_size", "vocab_size": "vocab_size",
                  "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
                  "tie_embeddings": "tie_word_embeddings"}
MATRICES = ("embed", "q", "k", "v", "o", "gate", "up", "down")


def shapes(m):
    return {"layers": m["num_hidden_layers"], "hidden": m["hidden_size"],
            "heads": m["num_attention_heads"], "kv_heads": m["num_key_value_heads"],
            "head_dim": m["hidden_size"] // m["num_attention_heads"],
            "ffn": m["intermediate_size"], "vocab": m["vocab_size"], "qkv_bias": True,
            "tied": True}


def program_config(m):
    out = {f: m[k] for f, k in PUBLISHED_KEYS.items()}
    out.update(head_dim=shapes(m)["head_dim"], qkv_bias=True)
    return out


def make_weights(m, seed, init, dtype=jnp.bfloat16):
    s = shapes(m)
    n, d, f, hd = s["layers"], s["hidden"], s["ffn"], s["head_dim"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    sizes = {"embed": (s["vocab"], d), "norm": (d,), "ln1": (n, d), "ln2": (n, d),
             "q": (n, q, d), "k": (n, kv, d), "v": (n, kv, d), "o": (n, d, q),
             "qb": (n, q), "kb": (n, kv), "vb": (n, kv),
             "gate": (n, f, d), "up": (n, f, d), "down": (n, d, f)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(sizes))
    out = {}
    for (name, shape), key in zip(sorted(sizes.items()), keys):
        z = jax.random.normal(key, shape)
        g = name in ("norm", "ln1", "ln2")
        out[name] = ((1.0 + init["norm_jitter"] * z) if g else init["init_std"] * z).astype(dtype)
    return out


def to_program(w):
    t = lambda a: jnp.swapaxes(a, -1, -2)
    return {"embed": {"tok": w["embed"], "final_norm": w["norm"]},
            "layers": {"attn": {"wq": t(w["q"]), "wk": t(w["k"]), "wv": t(w["v"]),
                                "wo": t(w["o"]), "bq": w["qb"], "bk": w["kb"], "bv": w["vb"]},
                       "mlp": {"wg": t(w["gate"]), "wi": t(w["up"]), "wo": t(w["down"])},
                       "ln1": w["ln1"], "ln2": w["ln2"]}}


def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def logits(w, m, tokens, rows):
    s = shapes(m)
    h, kvh, hd = s["heads"], s["kv_heads"], s["head_dim"]
    eps = m["rms_norm_eps"]
    w = {k: a.astype(jnp.float32) for k, a in w.items()}
    n = tokens.shape[0]
    pos = jnp.arange(n, dtype=jnp.float32)
    inv = m["rope_theta"] ** (-jnp.arange(0, hd, 2) / hd)
    ang = (pos[:, None] * inv)[:, None, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rot(x):
        a, b = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens]
        for i in range(s["layers"]):
            a = _norm(x, w["ln1"][i], eps)
            q = rot((a @ w["q"][i].T + w["qb"][i]).reshape(n, kvh, h // kvh, hd))
            k = rot((a @ w["k"][i].T + w["kb"][i]).reshape(n, kvh, 1, hd))[:, :, 0]
            v = (a @ w["v"][i].T + w["vb"][i]).reshape(n, kvh, hd)
            sc = jnp.einsum("qkgd,skd->kgqs", q, k) / jnp.sqrt(hd)
            sc = jnp.where(jnp.tril(jnp.ones((n, n), bool)), sc, -jnp.inf)
            att = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(sc, -1), v)
            x = x + att.reshape(n, h * hd) @ w["o"][i].T
            b = _norm(x, w["ln2"][i], eps)
            x = x + (jax.nn.silu(b @ w["gate"][i].T) * (b @ w["up"][i].T)) @ w["down"][i].T
        return _norm(x, w["norm"], eps)[rows] @ w["embed"].T


def decode_work(m, dtype_bytes, slots, live_positions):
    return lm_work.dense_decode(shapes(m), dtype_bytes, slots, live_positions)
'''


def test_a_later_lm_configuration_plugs_in_by_new_files_alone():
    """A second reference module, a configuration and a traffic mix as new
    data, and entries appended to the benchmark's lists: no edit to the
    harness or the LM driver."""
    name = f"_plug_{uuid.uuid4().hex[:8]}"
    path = BENCH / "lm_refs" / f"{name}.py"
    path.write_text(PLUG_REF)
    try:
        doc, traffic = tiny_lm(num_key_value_heads=2)
        doc.update(name="plug-gqa", reference=name)
        traffic.update(clients=3, requests=12, lengths_seed=3)
        spec = copy.deepcopy(SPEC)
        spec["configs"].append({"name": "plug-gqa", "source": doc["source"],
                                "file": "bench/configs/plug-gqa.json", "reduced": [],
                                "why": "plug-in test"})
        spec["workloads"].append({"name": "plug-chat3", "config": "plug-gqa",
                                  "traffic": "chat3", "chips": 1, "why": "plug-in test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if CELL in m.get("workloads", []):
                m["workloads"].append("plug-chat3")
        LM.validate(doc, traffic)
        res = run_lm(doc, traffic, spec=spec, workload="plug-chat3", seed=2**31 + 41)
        assert res["correct"] is True
        assert set(res["metrics"]) == {"tokens_per_s", "ttft_p50_ms", "itl_p50_ms", "setup_s"}
    finally:
        path.unlink()
        LM._REFS.pop(name, None)
