"""Batched serving engine with continuous batching (slot-based).

The engine holds a fixed pool of B slots. Requests are admitted into free
slots; each step advances EVERY active slot together; finished slots are
retired and refilled from the queue, vLLM-style, without ever re-lowering.

The slot/admission loop itself is workload-agnostic: :class:`Engine` owns
the queue, the slot occupancy, and the run loop, and delegates the actual
model work to an :class:`EngineAPI` backend:

* :class:`LMEngineCore` — LM token serving. One shared KV cache over the
  pool; prefill per-request at bucketed lengths, scattered into the slot's
  rows; each step decodes one token for every active slot (per-slot cache
  positions — the vectorized cache_pos path in models/layers.py). Works
  for every KV-cache family (dense/moe/vlm/audio); recurrent families
  (ssm/hybrid) serve through the same API with their O(1) state as the
  "cache".

* :class:`repro.serve.detector.DetectorEngineCore` — detection serving.
  Slot i is stream i of a vectorized streaming
  :class:`~repro.serve.detector.DetectorSession`; each step advances all
  active frame streams by one frame through the compile-once detector.

``Engine(cfg, params)`` dispatches on the config type (LMConfig vs
SNNDetConfig), so ``launch/serve.py --arch`` drives both workloads through
one loop.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LMConfig
from repro.models import zoo
from repro.serve.trace import NULL as NULL_TRACER


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


@dataclass
class Request:
    rid: int
    prompt: list  # token ids
    max_new_tokens: int = 32
    eos_id: int = -1  # -1 = never
    out: list = field(default_factory=list)
    done: bool = False


# ------------------------------------------------------ admission control --


@dataclass(frozen=True)
class AdmissionPolicy:
    """Backpressure contract for :meth:`Engine.submit`.

    ``max_queue`` bounds the number of QUEUED (not yet admitted) requests;
    ``None`` keeps the legacy unbounded queue. When the queue is full,
    ``on_full`` picks the policy:

    * ``"reject"`` — refuse the new request (it never enters the queue).
    * ``"shed-oldest"`` — evict queued requests from the FRONT until the
      new one fits (freshest traffic wins; a camera fleet cares about the
      latest frames, not a stale backlog).

    Either way the caller gets a typed :class:`SubmitResult` instead of
    silent queue growth, and every refused/evicted request lands in
    ``Engine.rejected`` with ``done=False``.
    """

    max_queue: Optional[int] = None
    on_full: str = "reject"

    def __post_init__(self):
        if self.on_full not in ("reject", "shed-oldest"):
            raise ValueError(
                f"on_full={self.on_full!r}: want 'reject' or 'shed-oldest'"
            )
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")


@dataclass(frozen=True)
class SubmitResult:
    """Typed outcome of one :meth:`Engine.submit` call. Truthy iff the
    request was accepted; ``reason`` explains a rejection (``"queue-full"``
    / ``"invalid: ..."``); ``shed`` lists requests evicted to make room
    under the shed-oldest policy."""

    accepted: bool
    reason: Optional[str] = None
    shed: tuple = ()

    def __bool__(self) -> bool:
        return self.accepted


class EngineRunResult(list):
    """`Engine.run`'s return value: the finished-request list (it IS a
    list, so existing callers keep working) plus the drain status.

    * ``status`` — ``"drained"`` (queue and slots empty) or ``"truncated"``
      (``max_steps`` exhausted with work left).
    * ``pending`` — requests that did NOT finish: in-flight slot occupants
      first, then the still-queued tail, every one with ``done=False``.
    """

    def __init__(self, finished, status: str, pending):
        super().__init__(finished)
        self.status = status
        self.pending = list(pending)

    @property
    def drained(self) -> bool:
        return self.status == "drained"


@runtime_checkable
class EngineAPI(Protocol):
    """Backend contract for the slot/admission loop.

    The Engine owns queue + slot occupancy; a backend only ever sees
    (request, slot index) pairs. ``admit`` loads one request's state into a
    slot (prefill / session reset); ``step`` advances every active slot by
    one unit of work (a token, a frame) and returns the slot indices that
    finished this step. Backends expose ``n_slots`` so the Engine can size
    its pool to match.
    """

    n_slots: int

    def admit(self, req: Any, slot_idx: int) -> None: ...

    def step(self, active: dict[int, Any]) -> list[int]: ...


class LMEngineCore:
    """EngineAPI backend for LM token serving over one shared KV cache."""

    def __init__(self, cfg: LMConfig, params, *, n_slots: int = 8,
                 max_seq: int = 512, greedy: bool = True):
        self.cfg = cfg
        self.api = zoo.get_api(cfg)
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.greedy = greedy
        self.pos = [0] * n_slots  # next cache write position per slot
        self.cache = self.api.init_cache(n_slots, max_seq)
        self._decode = jax.jit(self.api.decode_fn)
        self._prefill_cache = {}
        # bucketed prefill (pad + valid_len mask) holds for families whose
        # prefill cache is positionally sliceable — the causal mask keeps
        # positions < plen blind to the pad, and _scatter_kv only ever
        # copies rows [:plen] into the shared cache. Recurrent-state
        # families (ssm/hybrid) fold the whole padded sequence into their
        # O(1) state, so they keep exact-length prefill.
        self._bucketed = (
            getattr(cfg, "family", None) in ("dense", "moe")
            and not getattr(cfg, "kv_quant", False)
        )

    # ------------------------------------------------------------ prefill --
    def _prefill_fn(self, length: int):
        # one jit entry per BUCKET (pad + valid_len mask): the compile
        # cache is O(log max-prompt-len) under varied traffic instead of
        # one entry per exact prompt length. Non-bucketable families key
        # by exact length (their traffic decides the cache size).
        if length not in self._prefill_cache:
            self._prefill_cache[length] = jax.jit(self.api.prefill_fn)
        return self._prefill_cache[length]

    def admit(self, req: Request, slot_idx: int):
        plen = len(req.prompt)
        if self._bucketed:
            blen = _bucket(plen)
            padded = np.zeros((1, blen), np.int32)
            padded[0, :plen] = np.asarray(req.prompt, np.int32)
            logits, pcache = self._prefill_fn(blen)(
                self.params, jnp.asarray(padded), valid_len=jnp.int32(plen)
            )
        else:
            toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
            logits, pcache = self._prefill_fn(plen)(self.params, toks)
        tok = int(jnp.argmax(logits[0]))
        req.out.append(tok)
        self._scatter_kv(pcache, slot_idx, plen)
        self.pos[slot_idx] = plen

    def _scatter_kv(self, pcache, slot_idx: int, plen: int):
        """Copy the request's prefilled KV rows into the shared cache."""
        def put_kv(dst, src):
            """(L, B, S_max, kv, hd) <- (L, 1, plen, kv, hd) rows."""
            return dst.at[:, slot_idx, :plen].set(src[:, 0, :plen].astype(dst.dtype))

        def put_state(dst, src):
            """Recurrent state: copy the slot along whichever axis matches
            the pool size (no seq dim)."""
            for ax in range(dst.ndim):
                if dst.shape[ax] == self.n_slots and src.shape[ax] == 1:
                    idx = [slice(None)] * dst.ndim
                    idx[ax] = slot_idx
                    src_idx = [slice(None)] * src.ndim
                    src_idx[ax] = 0
                    return dst.at[tuple(idx)].set(src[tuple(src_idx)].astype(dst.dtype))
            return dst

        if hasattr(self.cache, "k"):  # dense KVCache
            self.cache = type(self.cache)(
                put_kv(self.cache.k, pcache.k), put_kv(self.cache.v, pcache.v)
            )
        elif hasattr(self.cache, "self_k"):  # whisper
            c = self.cache
            self.cache = type(c)(
                self_k=put_kv(c.self_k, pcache.self_k),
                self_v=put_kv(c.self_v, pcache.self_v),
                cross_k=c.cross_k.at[:, slot_idx].set(pcache.cross_k[:, 0].astype(c.cross_k.dtype)),
                cross_v=c.cross_v.at[:, slot_idx].set(pcache.cross_v[:, 0].astype(c.cross_v.dtype)),
            )
        elif hasattr(self.cache, "attn_k"):  # hybrid: KV + stacked states
            c = self.cache
            self.cache = type(c)(
                mamba=jax.tree_util.tree_map(put_state, c.mamba, pcache.mamba),
                tail=(
                    jax.tree_util.tree_map(put_state, c.tail, pcache.tail)
                    if c.tail is not None
                    else None
                ),
                attn_k=put_kv(c.attn_k, pcache.attn_k),
                attn_v=put_kv(c.attn_v, pcache.attn_v),
            )
        else:  # pure recurrent state pytrees (ssm)
            self.cache = jax.tree_util.tree_map(put_state, self.cache, pcache)

    # ------------------------------------------------------------- decode --
    def step(self, active: dict[int, Request]) -> list[int]:
        toks = np.zeros((self.n_slots,), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        for i, req in active.items():
            toks[i] = req.out[-1]
            pos[i] = self.pos[i]
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(pos)
        )
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        finished = []
        for i, req in active.items():
            self.pos[i] += 1
            tok = int(nxt[i])
            req.out.append(tok)
            if (
                tok == req.eos_id
                or len(req.out) >= req.max_new_tokens
                or self.pos[i] + 1 >= self.max_seq
            ):
                finished.append(i)
        return finished


def _resolve_core(cfg, params, *, n_slots, max_seq, greedy) -> EngineAPI:
    if isinstance(cfg, LMConfig):
        return LMEngineCore(cfg, params, n_slots=n_slots, max_seq=max_seq,
                            greedy=greedy)
    from repro.models.snn_yolo import SNNDetConfig, compile_detector
    from repro.serve.detector import CompiledDetector, DetectorEngineCore

    if isinstance(cfg, CompiledDetector):  # a pre-compiled handle
        return DetectorEngineCore(cfg, n_slots=n_slots)
    if isinstance(cfg, SNNDetConfig):
        if isinstance(params, tuple):  # (params, bn_state) as init_params returns
            p, bn = params
        else:
            p, bn = params, None
        return DetectorEngineCore(compile_detector(cfg, p, bn), n_slots=n_slots)
    raise TypeError(
        f"don't know how to serve {type(cfg).__name__}: pass an LMConfig, an "
        "SNNDetConfig, a CompiledDetector, or an explicit core="
    )


class Engine:
    """The workload-agnostic slot/admission loop over an EngineAPI core.

    ``admission`` bounds the queue (:class:`AdmissionPolicy`); ``submit``
    returns a typed :class:`SubmitResult` so callers see rejection/shedding
    instead of silent growth, and ``run`` reports whether the loop drained
    or truncated (:class:`EngineRunResult`).
    """

    def __init__(self, cfg=None, params=None, *, n_slots: int = 8,
                 max_seq: int = 512, greedy: bool = True,
                 core: Optional[EngineAPI] = None,
                 admission: Optional[AdmissionPolicy] = None):
        self.core = core if core is not None else _resolve_core(
            cfg, params, n_slots=n_slots, max_seq=max_seq, greedy=greedy
        )
        self.cfg = cfg
        self.admission = admission if admission is not None else AdmissionPolicy()
        self.n_slots = self.core.n_slots
        self.slots: list[Optional[Any]] = [None] * self.n_slots
        self.queue: list[Any] = []
        self.finished: list[Any] = []
        self.rejected: list[Any] = []  # refused/evicted requests (done=False)
        self._queued_at: dict[int, int] = {}  # id(request) -> submit time, ns

    @property
    def tracer(self):
        """The core's tracer (:class:`repro.serve.trace.Tracer`); a core
        without one serves untraced."""
        return getattr(self.core, "tracer", NULL_TRACER)

    def submit(self, req) -> SubmitResult:
        # reject malformed requests BEFORE they enter the queue: a bad
        # request discovered mid-run would otherwise abort the whole loop
        # (cores still validate again at admit time for direct-admit users)
        validate = getattr(self.core, "validate", None)
        if validate is not None:
            err = validate(req)
            if err is not None:
                self.rejected.append(req)
                return SubmitResult(False, reason=f"invalid: {err}")
        pol = self.admission
        if pol.max_queue is not None and len(self.queue) >= pol.max_queue:
            if pol.on_full == "reject":
                self.rejected.append(req)
                return SubmitResult(False, reason="queue-full")
            shed = []  # shed-oldest: evict the stale front, keep the fresh
            while len(self.queue) >= pol.max_queue:
                shed.append(self.queue.pop(0))
                self._queued_at.pop(id(shed[-1]), None)
            self.rejected.extend(shed)
            self._enqueue(req)
            return SubmitResult(True, reason="shed-oldest", shed=tuple(shed))
        self._enqueue(req)
        return SubmitResult(True)

    def _enqueue(self, req) -> None:
        if self.tracer.enabled:
            self._queued_at[id(req)] = time.time_ns()
        self.queue.append(req)

    def _admit(self, req, slot_idx: int) -> None:
        tr = self.tracer
        queued_at = self._queued_at.pop(id(req), None)
        rid = getattr(req, "rid", None)
        if queued_at is not None:
            tr.add("queued", queued_at, time.time_ns(), rid=rid)
        with tr.span("admit", rid=rid):
            self.core.admit(req, slot_idx)

    def _active(self) -> dict[int, Any]:
        return {i: r for i, r in enumerate(self.slots) if r is not None}

    def run(self, max_steps: int = 10_000) -> EngineRunResult:
        """Continuous-batching loop: admit from queue into free slots, then
        step all active slots together; repeat until drained (or until
        ``max_steps``, in which case the result's ``status`` is
        ``"truncated"`` and ``pending`` lists every undone request —
        in-flight occupants keep their slot state, so a later ``run()``
        resumes them).

        With an enabled tracer each iteration is one ``tick`` span (its id
        is the ``ticks`` counter before it), holding an ``admit`` span per
        admitted request and the core's spans; each admitted request's
        ``queued`` span runs from ``submit`` to its admission."""
        tr = self.tracer
        steps = 0
        while (self.queue or any(r is not None for r in self.slots)) and steps < max_steps:
            with tr.span("tick", tick=tr.counters.get("ticks", 0)):
                for i in range(self.n_slots):
                    if self.slots[i] is None and self.queue:
                        req = self.queue.pop(0)
                        self._admit(req, i)
                        self.slots[i] = req
                active = self._active()
                if active:
                    for i in self.core.step(active):
                        self.slots[i].done = True
                        self.finished.append(self.slots[i])
                        self.slots[i] = None
            tr.count("ticks")
            steps += 1
        pending = [r for r in self.slots if r is not None] + list(self.queue)
        return EngineRunResult(
            self.finished, "truncated" if pending else "drained", pending
        )
