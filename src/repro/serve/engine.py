"""Batched serving engine: continuous-batching-lite over jitted prefill /
decode steps, with straggler deadlines driven by the SimFA performance
predictor (the paper's model as a production feature — DESIGN.md §4).

Slots hold independent requests; finished slots are refilled from the queue
without stopping the decode loop. Designed so the decode step is the same
function the dry-run lowers for the decode_32k/long_500k cells.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new: int = 16
    out: List[int] = field(default_factory=list)
    done: bool = False


@dataclass
class StragglerPolicy:
    """Deadline-based step watchdog: expected step time comes from the
    SimFA predictor; steps slower than ``factor`` x expectation are counted
    and surfaced (on real fleets: triggers re-dispatch / hot-spare swap)."""
    expected_step_s: float = 0.1
    factor: float = 5.0
    slow_steps: int = 0

    def observe(self, dt: float) -> bool:
        slow = dt > self.factor * self.expected_step_s
        if slow:
            self.slow_steps += 1
        return slow

    @classmethod
    def from_samples(cls, samples, *, percentile: float = 0.99,
                     factor_floor: float = 1.5) -> "StragglerPolicy":
        """Calibrate from a sampled step-time distribution instead of a
        hand-picked factor — the fleet-serving consumer of
        ``repro.faults.sensitivity.step_time_samples``: Monte-Carlo the
        decode step under a seeded variability plan, then set the deadline
        where the *modeled* tail ends so only genuinely anomalous hosts
        trip it.  Expectation is the sample median; the factor is the
        p-``percentile``/median ratio (floored at ``factor_floor`` so a
        tight distribution still tolerates scheduler noise)."""
        xs = sorted(float(s) for s in samples)
        if not xs:
            return cls()
        med = xs[len(xs) // 2]
        hi = xs[min(len(xs) - 1, int(percentile * (len(xs) - 1)))]
        factor = max(factor_floor, hi / med if med > 0 else factor_floor)
        return cls(expected_step_s=med, factor=factor)


class ServeEngine:
    def __init__(self, cfg, params, *, slots: int = 4, max_seq: int = 256,
                 straggler: Optional[StragglerPolicy] = None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.straggler = straggler or StragglerPolicy()
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * slots
        self.cache = api.init_cache(cfg, slots, max_seq,
                                    dtype=jnp.dtype(cfg.compute_dtype))
        self.tokens = jnp.zeros((slots, 1), jnp.int32)
        from repro.serve.decode import make_serve_step
        self._decode = jax.jit(make_serve_step(cfg))
        self.steps = 0
        self.finished: List[Request] = []
        self.prompt_len: Optional[int] = None

    def submit(self, req: Request):
        # fixed prompt length per engine instance (scalar cache index);
        # production variant: per-slot index vector + length masking
        if self.prompt_len is None:
            self.prompt_len = len(req.prompt)
        assert len(req.prompt) == self.prompt_len, \
            "engine instance serves fixed-length prompts"
        self.queue.append(req)

    # --------------------------------------------------------------
    def _prefill_slot(self, slot: int, req: Request):
        """Single-request prefill into the shared cache (slot-batched). The
        prompt's last position gives the request's first token."""
        toks = jnp.asarray(req.prompt, jnp.int32)[None]
        hidden, cache1 = api.prefill(self.cfg, self.params, {"tokens": toks},
                                     max_seq=self.max_seq)
        logits = api.unembed(self.cfg, self.params, hidden[:, -1])
        first = int(jnp.argmax(logits[0]))
        slots = self.slots

        def splice(big, small):
            if small.ndim == 0:
                return big            # scalar index: set below
            for ax in range(big.ndim):
                if (big.shape[ax] == slots and small.shape[ax] == 1
                        and big.shape[:ax] == small.shape[:ax]
                        and big.shape[ax + 1:] == small.shape[ax + 1:]):
                    sl = [slice(None)] * big.ndim
                    sl[ax] = slice(slot, slot + 1)
                    return big.at[tuple(sl)].set(small.astype(big.dtype))
            return big

        self.cache = jax.tree.map(splice, self.cache, cache1)
        self.cache["idx"] = cache1["idx"]
        self.tokens = self.tokens.at[slot, 0].set(first)
        self._emit(slot, req, first)

    def _emit(self, slot: int, req: Request, tok: int):
        req.out.append(tok)
        if len(req.out) >= req.max_new:
            req.done = True
            self.active[slot] = None
            self.finished.append(req)

    def step(self):
        """One engine tick: refill empty slots, run one decode step."""
        for i in range(self.slots):
            if self.active[i] is None and self.queue:
                req = self.queue.pop(0)
                self.active[i] = req
                self._prefill_slot(i, req)
        if all(r is None for r in self.active):
            return False
        t0 = time.time()
        next_tok, self.cache = self._decode(self.params, self.cache, self.tokens)
        next_tok.block_until_ready()
        self.straggler.observe(time.time() - t0)
        self.tokens = next_tok
        self.steps += 1
        toks = np.asarray(next_tok)[:, 0]
        for i, req in enumerate(self.active):
            if req is not None:
                self._emit(i, req, int(toks[i]))
        return True

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Serve until the queue drains; returns the requests finished since
        the last ``run()``, in the order they finished."""
        while (self.queue or any(self.active)) and self.steps < max_steps:
            self.step()
        done, self.finished = self.finished, []
        return done
