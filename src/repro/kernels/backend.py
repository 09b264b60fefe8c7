"""Backend detection shared by every Pallas kernel wrapper (leaf module so
kernel files can use it without importing ops and creating a cycle)."""
from __future__ import annotations

import jax


def auto_interpret(interpret: bool | None = None) -> bool:
    """Resolve the Pallas ``interpret`` flag: explicit bool wins; ``None``
    auto-detects the backend (compiled Mosaic lowering on TPU, interpreter
    elsewhere — CPU/GPU have no lowering for these kernels)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def refuse_compiled_decoder(kernel: str, interpret: bool) -> None:
    """Raise unless ``interpret``: the in-kernel bitmask decoder (``cumsum``
    over the mask bits, then a ``take`` gather of the packed values) has no
    Mosaic lowering, so a kernel built on it runs in interpret mode only."""
    if not interpret:
        raise NotImplementedError(
            f"{kernel}: the in-kernel bitmask decoder (cumsum + gather) has "
            "no TPU lowering; it runs only with interpret=True. The compiled "
            "inference path is the fused kernel with predecoded weights "
            "(kernels.ops.fused_conv_bn_lif, predecode=True)."
        )


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` equations in ``fn``'s jaxpr (recursing into
    nested sub-jaxprs: pjit, scan, cond bodies). This is the DISPATCH COUNT
    of one traced execution — the verifiable form of "bit-serial encode
    executes as one dispatch" that kernel_bench and the conformance suite
    assert, independent of wall-clock noise."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)

    def walk(jp) -> int:
        n = 0
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for key in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                        "branches"):
                sub = eqn.params.get(key)
                if sub is None:
                    continue
                subs = sub if isinstance(sub, (tuple, list)) else [sub]
                for s in subs:
                    inner = getattr(s, "jaxpr", s)
                    if hasattr(inner, "eqns"):
                        n += walk(inner)
        return n

    return walk(closed.jaxpr)
