"""Serving engine: fused batched admission + donated decode over fixed slots.

``GenerationEngine`` owns a slot-sharded KV cache and two jitted entry
points shared (via an lru cache keyed on the hashable ``ModelConfig``)
across every engine replica of the same model:

- **fused admission** — all free slots are filled in ONE jitted call per
  prompt-length bucket: prompts are right-padded to the bucket length,
  prefilled as a batch, and the resulting rows are written *in place* into
  the donated slot cache (``.at[:, slot_idx].set`` under ``donate_argnums``
  lowers to an in-place scatter). The seed engine instead ran one eager
  per-request prefill plus an unjitted whole-tree ``.at[slot:slot+1].set``
  — an O(slots·max_len) copy of the full KV cache per admitted request.
  Right-padding is exact for attention layers (the decode kernels mask by
  ``lengths``; pad positions are never attended and are progressively
  overwritten), but recurrent layers (mamba 'm' / rwkv 'r') fold pad
  tokens into their state, so those patterns bucket by exact length.
- **fused decode** — one jitted step over all slots with
  ``donate_argnums`` on the cache and slot state, advancing every active
  slot, computing done-flags device-side, and returning ``(tokens, done)``
  so the host syncs ONCE per step instead of once per slot.

The engine serves from one compute-dtype copy of the weights. Every leaf
the model reads only through ``.astype(compute_dtype)``
(:func:`repro.models.compute_read`: the dense, GLU, attention, MoE-expert
and head weights and biases, and the embedding table) is cast once, in one
jitted program, and handed to the programs in place of the held leaf, so
each cast inside them is a no-op and a step reads the weights once in the
compute dtype (for bf16 over f32-held weights: a third of the bytes it
read, wrote and read back before). Leaves read in float32 (norm scales,
the MoE router, mamba's ``dt_proj``, rwkv's decay weights) and leaves
already in the compute dtype are passed through as they are. The copy is
shared: a module-level table, keyed on the source leaves and holding both
them and the copy weakly, hands every engine over the same params tree the
very same cast leaves, made once under a lock (fleet replicas come up from
their own threads, and a copy each would not fit beside the held weights).
Engines hold the copy; it is freed with the last of them.

Slot state lives on device between calls (lengths, token budgets, active
mask, last token per slot); the host keeps only the request objects and a
free-slot map. ``ContinuousBatcher`` fronts one engine with a thread-safe
per-tenant WRR :class:`~repro.serving.scheduler.SlotScheduler`;
``generate`` routes batch generation through the same engine path so there
is a single decode implementation.
"""
from __future__ import annotations

import functools
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import compute_read, decode_step, init_cache, prefill
from ..models.config import ModelConfig

from .scheduler import SlotScheduler


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # [S] int32
    max_new_tokens: int = 16
    tenant: str = "default"
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    submitted_at: float = field(default_factory=time.monotonic)
    dequeued_at: float = 0.0            # WRR dispatch (SlotScheduler.take)
    admit_started_at: float = 0.0       # prefill launch (before device sync)
    admitted_at: float = 0.0
    first_token_at: float = 0.0         # TTFT = first_token_at - submitted_at
    finished_at: float = 0.0


# --------------------------------------------------------------- jitted core

def _admit_kernel(cfg: ModelConfig, max_len: int, compute_dtype,
                  params, cache, slot_lengths, budget, active, last,
                  prompts, slot_idx, true_len, max_new):
    """Prefill ``k`` right-padded prompts and write them into freed slots.

    All slot-state updates are scatters at ``slot_idx`` on donated buffers;
    the full cache is never copied. Returns the updated slot state plus the
    first generated token per admitted row.
    """
    k = prompts.shape[0]
    row_cache = init_cache(cfg, k, max_len, enc_len=max_len)
    logits, row_cache, _ = prefill(params, cfg, prompts, row_cache,
                                   lengths=true_len,
                                   compute_dtype=compute_dtype)
    first = jnp.argmax(logits[:, 0, :cfg.vocab], axis=-1).astype(jnp.int32)
    cache = jax.tree.map(
        lambda c, rc: c.at[:, slot_idx].set(rc.astype(c.dtype)),
        cache, row_cache)
    slot_lengths = slot_lengths.at[slot_idx].set(true_len)
    # the first token is produced by the prefill itself: one unit of budget
    # is spent on it, and a slot stays active only if budget remains and
    # the cache can hold another token
    budget = budget.at[slot_idx].set(max_new - 1)
    active = active.at[slot_idx].set(
        (max_new > 1) & (true_len < max_len - 1))
    last = last.at[slot_idx, 0].set(first)
    return cache, slot_lengths, budget, active, last, first


def _step_kernel(cfg: ModelConfig, max_len: int, compute_dtype,
                 params, cache, slot_lengths, budget, active, last):
    """One decode step over every slot; inactive slots are masked out.

    Inactive slots still flow through the batched matmuls (their writes
    land at stale positions and are masked by ``lengths`` / overwritten at
    the next admission), which keeps the step shape static. Done-flags are
    reduced device-side so the host syncs once for the whole batch.
    """
    call_lengths = slot_lengths + 1     # new token position + 1
    logits, cache, _ = decode_step(params, cfg, last, cache, call_lengths,
                                   compute_dtype=compute_dtype)
    toks = jnp.argmax(logits[:, 0, :cfg.vocab], axis=-1).astype(jnp.int32)
    slot_lengths = jnp.where(active, slot_lengths + 1, slot_lengths)
    budget = jnp.where(active, budget - 1, budget)
    last = jnp.where(active[:, None], toks[:, None], last)
    done = active & ((budget <= 0) | (slot_lengths >= max_len - 1))
    active = active & ~done
    return cache, slot_lengths, budget, active, last, toks, done


@functools.lru_cache(maxsize=None)
def _compiled(cfg: ModelConfig, max_len: int, compute_dtype):
    """Jitted admit/step shared by every engine of this (cfg, max_len):
    replicas reuse traces instead of recompiling per instance."""
    admit = functools.partial(_admit_kernel, cfg, max_len, compute_dtype)
    step = functools.partial(_step_kernel, cfg, max_len, compute_dtype)
    admit.__name__, step.__name__ = "engine_admit", "engine_step"  # modules
    return (jax.jit(admit, donate_argnums=(1, 2, 3, 4, 5)),
            jax.jit(step, donate_argnums=(1, 2, 3, 4, 5)))


# ------------------------------------------------- compute-dtype weights

# (dtype, ids of the source leaves) -> (weakrefs to the source leaves,
# weakrefs to their cast copies); the table holds nothing alive
_copies: Dict[tuple, Tuple[list, list]] = {}
_copies_lock = threading.Lock()


@functools.lru_cache(maxsize=None)
def _caster(dtype):
    return jax.jit(lambda leaves: [x.astype(dtype) for x in leaves])


def _live(entry: Tuple[list, list]) -> Optional[list]:
    """The entry's cast leaves, while they and their sources all live (a
    live source is the leaf its id names in the key)."""
    got = [r() for r in entry[0] + entry[1]]
    return None if any(x is None for x in got) else got[len(entry[0]):]


def _serving_params(params: Any, compute_dtype) -> Tuple[Any, int]:
    """``params`` with each leaf the model reads only through
    ``.astype(compute_dtype)`` in ``compute_dtype``, and the number of
    copies made for it (0 or 1). Callers over the same source leaves share
    one copy while any of them holds it."""
    dtype = jnp.dtype(compute_dtype)
    leaves, treedef = jax.tree.flatten(params)
    reads = jax.tree.leaves(compute_read(params))
    todo = [i for i, (x, r) in enumerate(zip(leaves, reads))
            if r and x.dtype != dtype]
    if not todo:
        return params, 0
    src = [leaves[i] for i in todo]
    key = (dtype.name,) + tuple(map(id, src))
    with _copies_lock:
        cast = {k: _live(e) for k, e in _copies.items()}
        for k in [k for k, c in cast.items() if c is None]:
            del _copies[k]
        cast = cast.get(key)
        made = int(cast is None)
        if made:
            cast = _caster(dtype)(src)
            _copies[key] = ([weakref.ref(x) for x in src],
                            [weakref.ref(x) for x in cast])
    for i, x in zip(todo, cast):
        leaves[i] = x
    return jax.tree.unflatten(treedef, leaves), made


class GenerationEngine:
    """Slot-based engine: fused bucketed admission, donated joint decode.

    NOT thread-safe by itself: exactly one drive thread may call
    ``admit_many``/``step``; put a :class:`ContinuousBatcher` (or a fleet
    replica's drive thread) in front for concurrent submitters.
    """

    def __init__(self, cfg: ModelConfig, params: Any, *, slots: int = 4,
                 max_len: int = 512, compute_dtype=jnp.bfloat16):
        self.cfg = cfg
        # what the programs read, and the compute-dtype copies this engine
        # made for it (0 when it shares another engine's or needs none)
        self.params, self.weight_casts = _serving_params(params,
                                                        compute_dtype)
        self.slots = slots
        self.max_len = max_len
        self.compute_dtype = compute_dtype
        self.cache = init_cache(cfg, slots, max_len, enc_len=max_len)
        # device-resident slot state (donated through every fused call)
        self._slot_lengths = jnp.zeros((slots,), jnp.int32)
        self._budget = jnp.zeros((slots,), jnp.int32)
        self._active = jnp.zeros((slots,), bool)
        self._last = jnp.zeros((slots, 1), jnp.int32)
        # host mirrors (authoritative for slot occupancy)
        self.lengths = np.zeros((slots,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * slots
        self._admit_fn, self._step_fn = _compiled(cfg, max_len, compute_dtype)
        # recurrent state folds pad tokens in: bucket by exact length there
        self._exact_buckets = any(ch in cfg.layer_pattern for ch in "mr")
        # perf counters (benchmarks read these)
        self.steps = 0
        self.admit_calls = 0            # jitted admit invocations
        self.admitted = 0               # requests admitted
        self.full_cache_copies = 0      # whole-cache rescatter copies: stays 0
        self.host_syncs = 0             # device->host transfers

    # -- slots -------------------------------------------------------------

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _bucket(self, n: int) -> int:
        if self._exact_buckets:
            return n
        b = 8
        while b < n:
            b <<= 1
        return min(b, self.max_len - 1)

    # -- admission ---------------------------------------------------------

    def admit_many(self, reqs: List[Request]) -> List[Request]:
        """Admit up to ``len(free_slots())`` requests, one jitted call (and
        one host sync) per prompt-length bucket. Returns the requests
        admitted; those with ``done`` set finished at admission (their
        single-token budget was spent by the prefill)."""
        free = self.free_slots()
        take = [r for r in reqs[:len(free)]]
        if not take:
            return []
        groups: Dict[int, List[Request]] = {}
        for r in take:
            n = int(np.asarray(r.prompt).reshape(-1).shape[0])
            if n >= self.max_len:
                raise ValueError(
                    f"prompt length {n} >= engine max_len {self.max_len}")
            groups.setdefault(self._bucket(n), []).append(r)
        for pad_len, group in sorted(groups.items()):
            k = len(group)
            idx = np.asarray(free[:k], np.int32)
            free = free[k:]
            t_admit = time.monotonic()   # prefill launch, before host sync
            for r in group:
                r.admit_started_at = t_admit
            prompts = np.zeros((k, pad_len), np.int32)
            true_len = np.empty((k,), np.int32)
            max_new = np.empty((k,), np.int32)
            for j, r in enumerate(group):
                p = np.asarray(r.prompt, np.int32).reshape(-1)
                prompts[j, :p.shape[0]] = p
                true_len[j] = p.shape[0]
                max_new[j] = max(1, int(r.max_new_tokens))
            (self.cache, self._slot_lengths, self._budget, self._active,
             self._last, first) = self._admit_fn(
                self.params, self.cache, self._slot_lengths, self._budget,
                self._active, self._last, jnp.asarray(prompts),
                jnp.asarray(idx), jnp.asarray(true_len),
                jnp.asarray(max_new))
            first_np = jax.device_get(first)
            self.host_syncs += 1
            self.admit_calls += 1
            self.admitted += k
            now = time.monotonic()
            for j, r in enumerate(group):
                slot = int(idx[j])
                r.tokens.append(int(first_np[j]))
                r.admitted_at = now
                r.first_token_at = now
                if max_new[j] <= 1 or true_len[j] >= self.max_len - 1:
                    r.done = True
                    r.finished_at = now          # slot never occupied
                else:
                    self.slot_req[slot] = r
                    self.lengths[slot] = int(true_len[j])
        return take

    def admit(self, req: Request) -> bool:
        """Single-request admission (compat shim over ``admit_many``)."""
        return bool(self.admit_many([req]))

    # -- decode ------------------------------------------------------------

    def step(self) -> List[Request]:
        """One fused decode step over all slots; returns finished requests.
        One host sync per step regardless of slot count."""
        if not any(r is not None for r in self.slot_req):
            return []
        (self.cache, self._slot_lengths, self._budget, self._active,
         self._last, toks, done) = self._step_fn(
            self.params, self.cache, self._slot_lengths, self._budget,
            self._active, self._last)
        toks_np, done_np = jax.device_get((toks, done))
        self.host_syncs += 1
        self.steps += 1
        now = time.monotonic()
        finished: List[Request] = []
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            req.tokens.append(int(toks_np[i]))
            self.lengths[i] += 1
            if done_np[i]:
                req.done = True
                req.finished_at = now
                finished.append(req)
                self.slot_req[i] = None
                self.lengths[i] = 0
        return finished

    # -- introspection -----------------------------------------------------

    def active_slots(self) -> int:
        return sum(1 for r in self.slot_req if r is not None)

    def counters(self) -> Dict[str, int]:
        return {"steps": self.steps, "admit_calls": self.admit_calls,
                "admitted": self.admitted,
                "full_cache_copies": self.full_cache_copies,
                "host_syncs": self.host_syncs,
                "weight_casts": self.weight_casts}


class ContinuousBatcher:
    """Thread-safe request front for ONE engine: a per-tenant WRR
    :class:`SlotScheduler` feeds the engine's free slots. ``submit`` is
    safe from any thread; a single driver calls ``pump`` /
    ``run_until_drained``."""

    def __init__(self, engine: GenerationEngine,
                 scheduler: Optional[SlotScheduler] = None):
        self.engine = engine
        # NOT ``scheduler or ...``: SlotScheduler.__len__ is the pending
        # count, so a freshly-built (empty) scheduler is falsy and would be
        # silently replaced with a default fair one.
        self.scheduler = (scheduler if scheduler is not None
                          else SlotScheduler())
        self._lock = threading.Lock()
        self._uid = 0
        self.completed: Dict[int, Request] = {}

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               tenant: str = "default") -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.shape[0] >= self.engine.max_len:
            raise ValueError(f"prompt length {prompt.shape[0]} >= "
                             f"engine max_len {self.engine.max_len}")
        with self._lock:
            self._uid += 1
            uid = self._uid
        self.scheduler.submit(
            tenant, Request(uid, prompt, max_new_tokens, tenant=tenant))
        return uid

    def pump(self) -> List[Request]:
        """One admit+decode round; returns requests finished this round."""
        finished: List[Request] = []
        free = len(self.engine.free_slots())
        if free:
            for req in self.engine.admit_many(self.scheduler.take(free)):
                if req.done:
                    finished.append(req)
        finished.extend(self.engine.step())
        if finished:
            with self._lock:
                for req in finished:
                    self.completed[req.uid] = req
        return finished

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            self.pump()
            if (self.scheduler.pending() == 0
                    and self.engine.active_slots() == 0):
                return
        raise TimeoutError("batcher did not drain")


def generate(cfg: ModelConfig, params: Any, prompts: np.ndarray,
             max_new_tokens: int = 16, max_len: int = 256,
             compute_dtype=jnp.bfloat16) -> np.ndarray:
    """Batched generation routed through the engine path (ONE decode
    implementation): B prompts admit into B slots in a single fused call,
    then fused-decode to the token budget."""
    prompts = np.asarray(prompts, np.int32)
    B, S = prompts.shape
    if S + max_new_tokens > max_len:
        raise ValueError(f"prompt ({S}) + max_new_tokens ({max_new_tokens}) "
                         f"exceeds max_len ({max_len})")
    engine = GenerationEngine(cfg, params, slots=B, max_len=max_len,
                              compute_dtype=compute_dtype)
    reqs = [Request(i + 1, prompts[i], max_new_tokens) for i in range(B)]
    engine.admit_many(reqs)   # equal lengths: one bucket, slots 0..B-1
    while engine.active_slots():
        engine.step()
    return np.asarray([r.tokens for r in reqs])
