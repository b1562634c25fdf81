"""Batched serving engine: prefill + decode with KV/SSM cache and a simple
continuous-batching slot scheduler.

The engine is deliberately model-agnostic: it drives the ``lm_prefill`` /
``lm_decode_step`` entry points (or their enc-dec equivalents).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qpolicy import QuantLike
from repro.models import lm
from repro.models.config import ArchConfig


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 2048
    batch_slots: int = 8
    temperature: float = 0.0          # 0 => greedy
    eos_id: int = -1                  # -1 => never stop early
    cache_dtype: Any = jnp.float32    # dtype or string ("bfloat16", ...)
    #: bounded admission queue: ``submit`` raises :class:`QueueFull` beyond
    #: this — backpressure belongs at the edge, not as unbounded memory
    max_queue: int = 64
    #: default per-request deadline (seconds, wall clock from submit);
    #: ``None`` = no deadline.  Expired requests are evicted with whatever
    #: tokens they produced and recorded in ``ContinuousBatcher.failed``.
    default_deadline_s: Optional[float] = None

    def __post_init__(self):
        if isinstance(self.cache_dtype, str):
            # config files pass dtypes as strings; normalize once here so
            # init_cache and every jit signature see a real dtype object
            self.cache_dtype = jnp.dtype(self.cache_dtype)


class QueueFull(RuntimeError):
    """Admission queue at capacity — shed load at the edge instead of
    growing an unbounded backlog (``ServeConfig.max_queue``)."""


class Engine:
    """Slot-based continuous batching over a fixed decode batch."""

    def __init__(self, params, cfg: ArchConfig, qcfg: QuantLike,
                 scfg: ServeConfig):
        # qcfg: bare QuantConfig or path-scoped QuantPolicy — serve-time
        # decode resolves the same per-scope leaves as training, so a model
        # fine-tuned under a mixed policy serves under the identical one.
        self.params = params
        self.cfg = cfg
        self.qcfg = qcfg
        self.scfg = scfg
        self._decode = jax.jit(
            lambda p, t, c: lm.lm_decode_step(p, t, c, cfg, qcfg))
        # chunked prefill: one dispatch per prompt instead of one per token.
        # SSM/hybrid state recurrence has no cache-prefill form — those
        # families keep the universal token-step path.
        if cfg.family in ("ssm", "hybrid"):
            self._prefill = None
        else:
            self._prefill = jax.jit(
                lambda p, t, c: lm.lm_prefill_cache(p, t, c, cfg, qcfg))

    # -- single-shot batched generation ------------------------------------
    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 key: Optional[jax.Array] = None) -> np.ndarray:
        """prompts: (B, S) int32 (left-aligned, same length). Returns
        (B, max_new_tokens)."""
        B, S = prompts.shape
        cache = lm.init_cache(self.cfg, B, self.scfg.max_seq,
                              dtype=self.scfg.cache_dtype)
        # attention archs prefill the whole prompt in ONE dispatch through
        # the decode cache; state-carrying archs (SSM/hybrid) teacher-force
        # the prompt through decode steps (the recurrence has no cache-
        # prefill form).
        if self._prefill is not None:
            logits, cache = self._prefill(self.params, jnp.asarray(prompts),
                                          cache)
        else:
            logits = None
            for t in range(S):
                tok = prompts[:, t:t + 1]
                logits, cache = self._decode(self.params, jnp.asarray(tok),
                                             cache)
        out = []
        for i in range(max_new_tokens):
            nxt = self._sample(logits, None if key is None
                               else jax.random.fold_in(key, i))
            out.append(np.asarray(nxt))
            logits, cache = self._decode(self.params, nxt, cache)
        return np.concatenate(out, axis=1)

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        logits = logits[:, -1, : self.cfg.vocab]
        if self.scfg.temperature <= 0 or key is None:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.scfg.temperature)[:, None].astype(jnp.int32)


@dataclasses.dataclass
class _Slot:
    active: bool = False
    request_id: int = -1
    produced: int = 0
    budget: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    #: absolute ``time.monotonic()`` cutoff; None = no deadline
    deadline: Optional[float] = None


def _merge_slot(base: Dict[str, jax.Array], donor: Dict[str, jax.Array],
                slot: int) -> Dict[str, jax.Array]:
    """Cache whose ``slot``-th batch entry comes from ``donor``, everything
    else from ``base``. Batch is axis 1 for KV/SSM leaves (layer-stacked),
    axis 0 for the per-sequence ``index`` vector. Indexed ``.at[...].set``
    writes only the slot's row (one copy of ``base``, no full-cache select)."""
    out = {}
    for name, b in base.items():
        if name == "index":
            out[name] = b.at[slot].set(donor[name][slot])
        else:
            out[name] = b.at[:, slot].set(donor[name][:, slot])
    return out


def _merge_rows(base: jax.Array, donor: jax.Array, slot: int) -> jax.Array:
    """Row ``slot`` from ``donor``, the rest from ``base`` (batch axis 0)."""
    return base.at[slot].set(donor[slot])


class ContinuousBatcher:
    """Fixed-slot continuous batching: finished sequences free their slot,
    queued requests join mid-flight.

    Admission protocol: prefilling a new slot runs the *shared* batched
    prefill (one dispatch for the whole prompt; the per-token decode loop
    for SSM/hybrid), which advances and rewrites every slot's cache row and
    index — so admission snapshots the cache/logits first, resets only the
    admitted slot to fresh-cache state (per-slot ``index`` = 0, so the new
    request's tokens land at positions 0..P-1 exactly as in a solo run), and
    after prefill restores every *other* slot's row and index bit-exactly
    from the snapshot. Already-active slots therefore decode exactly as if
    the admission never happened, and admitted slots decode exactly as if
    they were alone — interleaved output == sequential output (regression:
    tests/test_serve.py::test_interleaved_matches_sequential).

    Single-token-step scheduling — the standard TPU decode regime where the
    batch dimension is the throughput lever.
    """

    def __init__(self, engine: Engine):
        self.engine = engine
        scfg = engine.scfg
        self.slots = [_Slot() for _ in range(scfg.batch_slots)]
        self.queue: List[Tuple[int, np.ndarray, int, Optional[float]]] = []
        self.results: Dict[int, np.ndarray] = {}
        #: request_id -> reason for every request that did not complete
        #: normally ("deadline", "nonfinite_logits"); partial output (possibly
        #: empty) still lands in ``results``
        self.failed: Dict[int, str] = {}
        self._next_id = 0
        B = scfg.batch_slots
        self.cache = lm.init_cache(engine.cfg, B, scfg.max_seq,
                                   dtype=scfg.cache_dtype)
        #: pristine cache used to reset a slot at admission (a freed slot
        #: still holds its previous occupant's KV/SSM state and index).
        self._fresh_cache = self.cache
        self.last_tok = jnp.zeros((B, 1), jnp.int32)
        self._logits: Optional[jax.Array] = None

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               deadline_s: Optional[float] = None) -> int:
        """Enqueue a request; raises :class:`QueueFull` when the admission
        queue is at ``max_queue`` (callers retry with backoff or shed).
        ``deadline_s`` (seconds from now; default ``default_deadline_s``)
        bounds queue wait + decode — expired requests are evicted with their
        partial output and show up in ``failed``."""
        if len(self.queue) >= self.engine.scfg.max_queue:
            raise QueueFull(
                f"admission queue at capacity ({self.engine.scfg.max_queue})")
        rid = self._next_id
        self._next_id += 1
        prompt = np.asarray(prompt).astype(np.int32)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if deadline_s is None:
            deadline_s = self.engine.scfg.default_deadline_s
        deadline = None if deadline_s is None else time.monotonic() + deadline_s
        self.queue.append((rid, prompt, max_new_tokens, deadline))
        return rid

    def _fail(self, rid: int, tokens: list, reason: str) -> None:
        self.results[rid] = np.asarray(tokens, dtype=np.int32)
        self.failed[rid] = reason

    def _evict(self, slot_id: int, reason: str) -> None:
        """Evict one slot: partial tokens become the result, the cache row
        is reset from the pristine cache so a poisoned row (non-finite KV
        state) cannot linger in the shared batch."""
        s = self.slots[slot_id]
        self._fail(s.request_id, s.tokens, reason)
        self.cache = _merge_slot(self.cache, self._fresh_cache, slot_id)
        self.slots[slot_id] = _Slot()

    def _pop_live(self):
        """Next queued request whose deadline has not already expired;
        expired ones fail immediately with an empty result."""
        while self.queue:
            rid, prompt, budget, deadline = self.queue.pop(0)
            if deadline is not None and time.monotonic() > deadline:
                self._fail(rid, [], "deadline")
                continue
            return rid, prompt, budget, deadline
        return None

    def _admit(self) -> None:
        for slot_id, s in enumerate(self.slots):
            if s.active:
                continue
            nxt = self._pop_live()
            if nxt is None:
                return
            rid, prompt, budget, deadline = nxt
            # snapshot: prefill below steps the shared decode function, which
            # touches every slot's cache row/index and logits.
            snap_cache, snap_logits = self.cache, self._logits
            # reset the admitted slot to fresh-cache state.
            self.cache = _merge_slot(self.cache, self._fresh_cache, slot_id)
            if self.engine._prefill is not None:
                # one chunked-prefill dispatch: the admitted slot's prompt in
                # its row, zeros elsewhere — the other rows advance through
                # garbage and are restored bit-exactly from the snapshot.
                toks = np.zeros((len(self.slots), len(prompt)), np.int32)
                toks[slot_id] = prompt
                logits, self.cache = self.engine._prefill(
                    self.engine.params, jnp.asarray(toks), self.cache)
            else:
                logits = None
                for t in range(len(prompt)):
                    tok = np.array(self.last_tok)     # writable copy
                    tok[slot_id, 0] = prompt[t]
                    self.last_tok = jnp.asarray(tok)
                    logits, self.cache = self.engine._decode(
                        self.engine.params, self.last_tok, self.cache)
            # restore every other slot bit-exactly from the snapshot.
            self.cache = _merge_slot(snap_cache, self.cache, slot_id)
            if snap_logits is not None:
                logits = _merge_rows(snap_logits, logits, slot_id)
            self.slots[slot_id] = _Slot(active=True, request_id=rid,
                                        produced=0, budget=budget, tokens=[],
                                        deadline=deadline)
            self._logits = logits

    def step(self) -> None:
        self._admit()
        if not any(s.active for s in self.slots):
            return
        # health pass before sampling: expired deadlines and poisoned slots
        # (non-finite logits row — a blown-up integer decode in ONE sequence)
        # evict that slot only; the rest of the batch keeps decoding.
        now = time.monotonic()
        logits_np: Optional[np.ndarray] = None
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            if s.deadline is not None and now > s.deadline:
                self._evict(i, "deadline")
                continue
            if logits_np is None:
                logits_np = np.asarray(
                    self._logits[:, -1, : self.engine.cfg.vocab])
            if not np.isfinite(logits_np[i]).all():
                self._evict(i, "nonfinite_logits")
        if not any(s.active for s in self.slots):
            return
        nxt = self.engine._sample(self._logits, None)
        nxt_np = np.asarray(nxt)
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            s.tokens.append(int(nxt_np[i, 0]))
            s.produced += 1
            done = s.produced >= s.budget or (
                self.engine.scfg.eos_id >= 0
                and s.tokens[-1] == self.engine.scfg.eos_id)
            if done:
                self.results[s.request_id] = np.asarray(s.tokens)
                self.slots[i] = _Slot()
        self.last_tok = nxt
        self._logits, self.cache = self.engine._decode(
            self.engine.params, self.last_tok, self.cache)

    def run_until_drained(self, max_steps: int = 100000) -> Dict[int, np.ndarray]:
        for _ in range(max_steps):
            if not self.queue and not any(s.active for s in self.slots):
                break
            self.step()
        return self.results
