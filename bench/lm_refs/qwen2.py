"""Plain reference of the Qwen2 decoder (Qwen1.5), the benchmark's weights
for it, and their converter to the program's layout.

The forward pass follows the published architecture (``Qwen2ForCausalLM``
of the ``transformers`` library, which Qwen1.5 checkpoints name in their
``config.json``) in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``, with no cache, batching or
kernel: it imports nothing of the program. Per layer, pre-norm:

* RMSNorm: ``x * rsqrt(mean(x^2) + rms_norm_eps) * g``;
* attention: q, k and v projections with biases (Qwen2's ``q_proj``,
  ``k_proj`` and ``v_proj`` carry one; ``o_proj`` does not), rotary position
  embedding in the rotate-half form, ``theta = rope_theta``, over the whole
  head, causal softmax of ``q k / sqrt(head_dim)``, ``num_key_value_heads``
  heads of k and v shared by ``num_attention_heads / num_key_value_heads``
  query heads each;
* SwiGLU MLP: ``down(silu(gate(x)) * up(x))``;
* a final RMSNorm and the head, tied to the embedding where
  ``tie_word_embeddings`` says so.

Departures from the published model, each the same in the program and the
reference: the weights are random (the benchmark's own, below), and no
sliding window is modelled (the configuration has ``use_sliding_window``
false, and the cell's contexts are far inside ``max_position_embeddings``).

Weights (``make_weights``): one jitted call from the configuration's weight
seed, in the type they are served in, in this module's layout: matrices
``(in, out)`` stacked over the layers, the embedding ``(vocab, hidden)``.
Matrices, biases and the embedding are normal with the configuration's
``init_std`` (the published ``initializer_range``); RMSNorm gains are
``1 + norm_jitter * normal``, so that a program that dropped a gain or a
bias would read differently. ``to_program`` lays them out as the program's
``params`` pytree (``repro.models.transformer``: ``embed.tok``,
``embed.final_norm``, ``layers.{attn,mlp,ln1,ln2}`` stacked over layers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import lm_work

# the program's LMConfig field for each published key this reference reads
PUBLISHED_KEYS = {
    "n_layers": "num_hidden_layers",
    "d_model": "hidden_size",
    "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads",
    "d_ff": "intermediate_size",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "norm_eps": "rms_norm_eps",
    "tie_embeddings": "tie_word_embeddings",
}
LAYER_LEAVES = ("ln1", "q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w",
                "ln2", "gate_w", "up_w", "down_w")
# the weight matrices (stacked over layers) and the embedding tables; the
# rest are vectors: norm gains and biases
MATRICES = ("embed", "lm_head", "q_w", "k_w", "v_w", "o_w", "gate_w", "up_w", "down_w")


def shapes(model: dict) -> dict:
    """The sizes this reference reads from a configuration's ``model``."""
    heads = model["num_attention_heads"]
    return {"layers": model["num_hidden_layers"], "hidden": model["hidden_size"],
            "heads": heads, "kv_heads": model["num_key_value_heads"],
            "head_dim": model.get("head_dim") or model["hidden_size"] // heads,
            "ffn": model["intermediate_size"], "vocab": model["vocab_size"],
            "qkv_bias": True, "tied": model["tie_word_embeddings"]}


def program_config(model: dict) -> dict:
    """The program's config fields as the published model states them."""
    out = {field: model[key] for field, key in PUBLISHED_KEYS.items()}
    out["head_dim"] = shapes(model)["head_dim"]
    out["qkv_bias"] = True
    return out


def leaf_shapes(model: dict) -> dict:
    s = shapes(model)
    n, d, f = s["layers"], s["hidden"], s["ffn"]
    q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    out = {"embed": (s["vocab"], d), "final_norm": (d,),
           "ln1": (n, d), "q_w": (n, d, q), "q_b": (n, q), "k_w": (n, d, kv),
           "k_b": (n, kv), "v_w": (n, d, kv), "v_b": (n, kv), "o_w": (n, q, d),
           "ln2": (n, d), "gate_w": (n, d, f), "up_w": (n, d, f), "down_w": (n, f, d)}
    if not s["tied"]:
        out["lm_head"] = (d, s["vocab"])
    return out


def make_weights(model: dict, seed: int, init: dict, dtype=jnp.bfloat16) -> dict:
    """The benchmark's weights, from ``seed``, on the device, in ``dtype``."""
    leaves = leaf_shapes(model)
    names = sorted(leaves)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for name, k in zip(names, keys):
            z = jax.random.normal(k, leaves[name], jnp.float32)
            if name in ("ln1", "ln2", "final_norm"):
                w = 1.0 + init["norm_jitter"] * z
            else:
                w = init["init_std"] * z
            out[name] = w.astype(dtype)
        return out

    return make(jax.random.PRNGKey(seed % 2**32))


@jax.jit
def to_program(w: dict) -> dict:
    """The weights as the program's ``params`` pytree."""
    embed = {"tok": w["embed"], "final_norm": w["final_norm"]}
    if "lm_head" in w:
        embed["lm_head"] = w["lm_head"]
    return {
        "embed": embed,
        "layers": {
            "attn": {"wq": w["q_w"], "wk": w["k_w"], "wv": w["v_w"], "wo": w["o_w"],
                     "bq": w["q_b"], "bk": w["k_b"], "bv": w["v_b"]},
            "mlp": {"wg": w["gate_w"], "wi": w["up_w"], "wo": w["down_w"]},
            "ln1": w["ln1"],
            "ln2": w["ln2"],
        },
    }


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, positions, theta):
    """Rotate-half RoPE over the whole head. x: (S, heads, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("model_items",))
def _hidden(w, tokens, model_items):
    """Final-normed hidden states (S, hidden) of one sequence, float32."""
    model = dict(model_items)
    s = shapes(model)
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h, kvh, hd = s["heads"], s["kv_heads"], s["head_dim"]
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    n = tokens.shape[0]
    pos = jnp.arange(n)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"][tokens]

    def layer(x, lw):
        a = _rmsnorm(x, lw["ln1"], eps)
        q = (a @ lw["q_w"] + lw["q_b"]).reshape(n, h, hd)
        k = (a @ lw["k_w"] + lw["k_b"]).reshape(n, kvh, hd)
        v = (a @ lw["v_w"] + lw["v_b"]).reshape(n, kvh, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
        x = x + att.reshape(n, h * hd) @ lw["o_w"]
        m = _rmsnorm(x, lw["ln2"], eps)
        x = x + (jax.nn.silu(m @ lw["gate_w"]) * (m @ lw["up_w"])) @ lw["down_w"]
        return x, None

    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in LAYER_LEAVES})
    return _rmsnorm(x, w["final_norm"], eps)


@jax.jit
def _head(w, hidden, rows):
    table = w["lm_head"] if "lm_head" in w else w["embed"].T
    return hidden[rows] @ table.astype(jnp.float32)


def logits(w: dict, model: dict, tokens, rows):
    """Float32 logits at positions ``rows`` of one sequence ``tokens``.

    Both are padded by the caller to a few fixed lengths, so that a handful
    of programs serve every sequence: the attention is causal, so tokens
    past a row do not change it. The matrix products run at ``highest``
    precision (the setting is read when the programs are compiled)."""
    items = tuple(sorted((k, v) for k, v in model.items()
                         if isinstance(v, (int, float, bool, str))))
    with jax.default_matmul_precision("highest"):
        return _head(w, _hidden(w, tokens, items), rows)


def decode_work(model: dict, dtype_bytes: int, slots: int, live_positions: int) -> dict:
    """Operations and bytes one decode step asks for (``lm_work``)."""
    return lm_work.dense_decode(shapes(model), dtype_bytes, slots, live_positions)
