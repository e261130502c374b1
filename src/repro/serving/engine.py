"""Continuous-batching serving engine with WarmSwap-backed replica bring-up.

The engine owns a fixed pool of decode slots over one batched decode state:

  * ``submit()`` queues requests; admission prefills each (B=1, its own length) and
    splices the resulting KV/recurrent state into a free slot — in-flight requests
    never stall behind a new prefill longer than one engine step;
  * ``step()`` runs one batched ``serve_step`` for ALL slots (parked slots decode
    garbage into their own ring slot — harmless, reset on admission) and retires
    finished requests (EOS or token budget);
  * per-slot position streams come from the per-batch ``k_pos``/``pos`` machinery in
    the model, so slots at different depths coexist in one jitted step.

Replica bring-up is WarmSwap's job: ``ServingEngine.from_pool`` live-migrates the
base-model image out of the DependencyManager (compile-cache + page stream) instead
of cold-loading from a store — this is also the node-failure recovery path
(runtime/fault_tolerance.py measures it). Parameters built on the device are
restored to the device: their host-to-device transfer is part of bring-up,
charged to migration, and no prefill or decode step copies them again
(``h2d_bytes`` is 0).
"""
from __future__ import annotations

import collections
import gc
import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import make_serve_step_with_logits
from repro.models.config import ArchConfig
from repro.models.transformer import forward, init_decode_state
from repro.runtime.tracing import span
from repro.serving.state_utils import state_reset_slot, state_splice


@dataclass
class ServeConfig:
    max_slots: int = 4
    max_seq_len: int = 512
    max_new_tokens: int = 32
    eos_id: int = -1                # -1: disabled (synthetic vocab has no EOS)
    temperature: float = 0.0        # 0 = greedy
    seed: int = 0


@dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int
    submitted_at: float = field(default_factory=time.monotonic)
    prefilled_at: Optional[float] = None
    finished_at: Optional[float] = None
    tokens: List[int] = field(default_factory=list)

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.prefilled_at is None else self.prefilled_at - self.submitted_at

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.finished_at is None else self.finished_at - self.submitted_at


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params: Any,
                 serve_cfg: Optional[ServeConfig] = None):
        self.cfg = cfg
        self.params = params
        self.scfg = serve_cfg if serve_cfg is not None else ServeConfig()
        B = self.scfg.max_slots
        with span("engine_init", slots=B, max_seq_len=self.scfg.max_seq_len):
            self.state = init_decode_state(cfg, B, self.scfg.max_seq_len,
                                           jnp.float32)
            self._serve_step = jax.jit(make_serve_step_with_logits(cfg))
        # bytes of the parameter leaves not on the device: the least that
        # every prefill and decode step copies host->device
        self.h2d_bytes = sum(np.asarray(x).nbytes for x in jax.tree.leaves(params)
                             if not isinstance(x, jax.Array))
        self._queue: Deque[Request] = collections.deque()
        self._slots: List[Optional[Request]] = [None] * B
        self._next_tok = np.zeros((B, 1), np.int32)
        self._rid = itertools.count()
        self.completed: Dict[int, Request] = {}
        self._rng = np.random.default_rng(self.scfg.seed)
        self.steps = 0

    # ------------------------------------------------------------------ intake
    def submit(self, prompt: np.ndarray, max_new_tokens: Optional[int] = None) -> int:
        req = Request(next(self._rid), np.asarray(prompt, np.int32),
                      max_new_tokens or self.scfg.max_new_tokens)
        self._queue.append(req)
        return req.rid

    # ------------------------------------------------------------------ admission
    def _admit(self) -> None:
        for slot in range(self.scfg.max_slots):
            if self._slots[slot] is not None or not self._queue:
                continue
            req = self._queue.popleft()
            queued_us = int(1e6 * (time.monotonic() - req.submitted_at))
            with span("admit", rid=req.rid, prompt_len=len(req.prompt),
                      queued_us=queued_us):
                self._admit_one(req, slot)

    def _admit_one(self, req: Request, slot: int) -> None:
        with span("prefill", rid=req.rid, h2d_bytes=self.h2d_bytes):
            tokens = jnp.asarray(req.prompt[None, :], jnp.int32)
            logits, _, single = forward(
                self.params, tokens, self.cfg, make_state=True,
                state_len=self.scfg.max_seq_len, logits_slice=1)
            last = np.asarray(logits[:, -1, : self.cfg.vocab_size])
        with span("sample", active=1):
            first = self._sample(last)
        req.prefilled_at = time.monotonic()
        req.tokens.append(int(first[0]))
        with span("splice", rid=req.rid):
            self.state = state_reset_slot(self.state, slot)
            self.state = state_splice(self.state, single, slot)
        self._slots[slot] = req
        self._next_tok[slot, 0] = first[0]

    def _sample(self, logits: np.ndarray) -> np.ndarray:
        if self.scfg.temperature <= 0:
            return np.argmax(logits, axis=-1).astype(np.int32)
        z = logits / self.scfg.temperature
        z = z - z.max(-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(-1, keepdims=True)
        return np.array([self._rng.choice(len(row), p=row) for row in p], np.int32)

    # ------------------------------------------------------------------ one step
    def step(self) -> int:
        """Admit, decode one token for every active slot; returns #active."""
        with span("step", queued=len(self._queue)):
            self._admit()
            active = [i for i, r in enumerate(self._slots) if r is not None]
            if not active:
                return 0
            with span("decode", active=len(active), h2d_bytes=self.h2d_bytes):
                logits, self.state = self._serve_step(
                    self.params, self.state, jnp.asarray(self._next_tok))
                logits = np.asarray(logits)
            with span("sample", active=len(active)):
                self._retire(active, self._sample(logits))
            return len(active)

    def _retire(self, active: List[int], toks: np.ndarray) -> None:
        """Appends each active slot's token and frees finished slots."""
        self.steps += 1
        now = time.monotonic()
        for slot in active:
            req = self._slots[slot]
            req.tokens.append(int(toks[slot]))
            self._next_tok[slot, 0] = toks[slot]
            done = (len(req.tokens) >= req.max_new_tokens or
                    (self.scfg.eos_id >= 0 and toks[slot] == self.scfg.eos_id))
            if done:
                req.finished_at = now
                self.completed[req.rid] = req
                self._slots[slot] = None

    def run_until_done(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not self._queue and all(s is None for s in self._slots):
                return
            self.step()

    # ------------------------------------------------------------------ bring-up
    @classmethod
    def from_pool(cls, manager, image_id: str, cfg: ArchConfig,
                  serve_cfg: Optional[ServeConfig] = None, policy=None):
        """WarmSwap replica bring-up: live-migrate the base image from the pool."""
        from repro.core.migration import RestorePolicy
        policy = policy or RestorePolicy.BULK
        with span("from_pool", policy=policy.value):
            # a dropped replica caught in a reference cycle keeps its
            # parameters and decode state in device memory until the cyclic
            # collector runs: return that memory before claiming a new one's.
            # bench/systems/endpoint.py makes such cycles (it stores wrappers
            # of a replica's methods on the replica); no caller in src/ does.
            gc.collect()
            restored = manager.request_migration(image_id, policy)
            params = restored.as_pytree()
            manager.release(image_id)
            return cls(cfg, params, serve_cfg)

    # ------------------------------------------------------------------ metrics
    def metrics(self) -> Dict[str, float]:
        done = list(self.completed.values())
        if not done:
            return {"completed": 0}
        return {
            "completed": len(done),
            "mean_ttft_s": float(np.mean([r.ttft_s for r in done])),
            "mean_latency_s": float(np.mean([r.latency_s for r in done])),
            "engine_steps": self.steps,
        }
