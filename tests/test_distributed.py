"""Distributed helpers under a real multi-device mesh. These tests spawn a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the
parent process has already initialized jax with 1 device)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

_ENV = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": "src"}


def _run(body: str):
    code = textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], env=_ENV, cwd=os.path.dirname(os.path.dirname(__file__)),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_int8_psum_and_hierarchical():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed import collectives as C
        from repro.distributed.meshes import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        x = jnp.arange(24, dtype=jnp.float32).reshape(4, 6) / 7.0
        with mesh:
            y = jax.jit(C.int8_psum(mesh, "data"))(x)
            # replicated input -> psum over data multiplies by the axis size;
            # two int8 rounding passes vs the row max: atol = 2*2*max/127
            atol = 4 * float(jnp.max(jnp.abs(x))) / 127
            np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 2, atol=atol)
            z = jax.jit(C.hierarchical_psum(mesh))(x)
            np.testing.assert_allclose(np.asarray(z), np.asarray(x) * 4, rtol=1e-5)
        print("COLLECTIVES_OK")
    """)
    assert "COLLECTIVES_OK" in out


def test_overlap_allgather_matmul():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import collectives as C
        from repro.distributed.meshes import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
        with mesh:
            wsh = jax.device_put(w, NamedSharding(mesh, P("model", None)))
            y = jax.jit(C.overlap_allgather_matmul(mesh, "model"))(x, wsh)
            np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=1e-4, atol=1e-4)
        print("OVERLAP_OK")
    """)
    assert "OVERLAP_OK" in out


def test_distributed_embedding_grads_sharded():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding as shd, embedding as de
        from repro.distributed.meshes import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = shd.default_rules(mesh, fsdp=True)
        V, D, B, S = 32, 16, 4, 8
        table = jax.random.normal(jax.random.PRNGKey(0), (V, D))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, V)
        with mesh, shd.use_rules(rules, mesh=mesh):
            tsh = jax.device_put(table, NamedSharding(mesh, P("model", "data")))
            tok = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
            out = jax.jit(de.embed_lookup)(tok, tsh)
            np.testing.assert_allclose(np.asarray(out), np.asarray(table[tokens]), atol=1e-6)
            g = jax.jit(jax.grad(lambda t: jnp.sum(de.embed_lookup(tok, t) ** 2)))(tsh)
            np.testing.assert_allclose(
                np.asarray(g),
                np.asarray(jax.grad(lambda t: jnp.sum((t[tokens]) ** 2))(table)),
                rtol=1e-4, atol=1e-5)
            # THE point: the gradient arrives sharded, not replicated
            assert g.sharding.spec == P("model", "data"), g.sharding
        print("EMBED_OK")
    """)
    assert "EMBED_OK" in out


def test_kvops_seq_sharded_write():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding as shd, kvops
        from repro.distributed.meshes import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = shd.default_rules(mesh)
        L_, B, S, KV, HD = 3, 2, 16, 2, 4
        buf = jnp.zeros((L_, B, S, KV, HD), jnp.float32)
        val = jnp.ones((B, 1, KV, HD), jnp.float32) * 7
        with mesh, shd.use_rules(rules, mesh=mesh):
            bsh = jax.device_put(buf, NamedSharding(mesh, P(None, "data", "model", None, None)))
            for layer, pos in ((0, 0), (1, 5), (2, 13)):  # hits different shards
                new = jax.jit(kvops.cache_write)(bsh, val, jnp.int32(layer), jnp.int32(pos))
                ref = buf.at[layer, :, pos].set(7.0)
                np.testing.assert_array_equal(np.asarray(new), np.asarray(ref))
        print("KVOPS_OK")
    """)
    assert "KVOPS_OK" in out
