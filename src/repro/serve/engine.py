"""Batched serving engine: continuous-batching-lite over jitted prefill /
decode steps, with a step-time watchdog (``StragglerPolicy``) whose
deadline the caller sets.

Slots hold independent requests; finished slots are refilled from the queue
without stopping the decode loop. The decode step is the function the
dry-run lowers (``serve.decode.make_serve_step``). The prefill
and unembed of one request are one program too (``make_prefill_step``),
compiled at the engine's first admission for its fixed prompt length and
only dispatched after that; ``ServeEngine.prefill_compiles`` counts the
prefill programs the engine has built (1 after any number of admissions).
The engine compiles the decode step once, with the cache donated and its
axes stored in the order of the layout the compiler chose
(``serve.decode.compile_serve_step``); the cache is made, and each
admission spliced into it, in that order and in place.

Each phase of ``step()`` is a host span in the profiler's trace, recorded
only while a profile is being taken (``jax.profiler.start_trace``):

- ``serve.step``: the whole of ``step()``; its time outside its children
  is the slot loop and other bookkeeping.
- ``serve.admit`` (stats ``rid``, ``slot``): one request's admission, made
  of ``serve.prefill`` (the dispatch of the compiled prefill; at the
  engine's first admission, its compile too), ``serve.first_token`` (the
  wait for its first token) and ``serve.splice`` (its cache and token into
  the slot).
- ``serve.decode``: the call of the jitted decode step (dispatch);
  ``serve.decode_wait``: the wait for it; ``serve.fetch``: the new tokens
  copied to the host.

Where the model's MoE layers hold a share of the experts
(``cfg.reports_routing``), each decode step also returns its routed
counts, fetched with the tokens: ``ServeEngine.routed`` gets one
(MoE layers, held experts) array a step, the (token, choice) pairs each
held expert was given, since the latest ``run()`` began (a caller that
drives ``step()`` itself clears it).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import api
from repro.serve.decode import (cache_axes, compile_serve_step,
                                make_prefill_step, store)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new: int = 16
    out: List[int] = field(default_factory=list)
    done: bool = False
    # time.perf_counter() when the engine queued the request, began its
    # prefill and gave its first token; None until then
    t_queued: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None


@dataclass
class StragglerPolicy:
    """Deadline-based step watchdog: the caller gives the expected step
    time (or ``from_samples`` sets it from sampled step times); steps
    slower than ``factor`` x expectation are counted and surfaced (on real
    fleets: triggers re-dispatch / hot-spare swap)."""
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


def _make_splice(slots: int, axes):
    def splice(cache, one, slot):
        """``one``, a single request's cache, into batch entry ``slot`` of
        ``cache`` (``slots`` entries, stored in the order ``axes`` gives);
        its scalar index replaces the cache's."""
        def put(big, small):
            if small.ndim == 0:
                return small
            for ax in range(big.ndim):
                if (big.shape[ax] == slots and small.shape[ax] == 1
                        and big.shape[:ax] == small.shape[:ax]
                        and big.shape[ax + 1:] == small.shape[ax + 1:]):
                    return jax.lax.dynamic_update_slice_in_dim(
                        big, small.astype(big.dtype), slot, axis=ax)
            # left as it was, the slot would keep its last request's state
            raise ValueError(f"no batch axis of {slots} in a cache array of "
                             f"shape {big.shape} for one of {small.shape}")
        return jax.tree.map(put, cache, store(one, axes))
    return splice


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
        tokens = jnp.zeros((slots, 1), jnp.int32)
        # committed to its device, as the decode step's outputs that take
        # the place of it and of the cache are: the splice and the token
        # write then compile once, not again when those come back
        self.tokens = jax.device_put(tokens, tokens.sharding)
        shape = dict(batch=slots, max_seq=max_seq,
                     sharding=self.tokens.sharding)
        self.cache_axes = cache_axes(cfg, params, **shape)
        self._decode = compile_serve_step(cfg, params, self.cache_axes,
                                          **shape)
        self.cache = jax.jit(lambda: store(api.init_cache(
            cfg, slots, max_seq, dtype=jnp.dtype(cfg.compute_dtype)),
            self.cache_axes), out_shardings=self.tokens.sharding)()
        self._splice = jax.jit(_make_splice(slots, self.cache_axes),
                               donate_argnums=(0,))
        mem = self._decode.memory_analysis()
        # bytes the step writes into its donated inputs, and its scratch:
        # the cache's size and under one layer's K when it is updated in
        # place; None where the backend does not say
        self.decode_alias_bytes = getattr(mem, "alias_size_in_bytes", None)
        self.decode_temp_bytes = getattr(mem, "temp_size_in_bytes", None)
        self.steps = 0
        self.finished: List[Request] = []
        self.routed: List[np.ndarray] = []
        self.prompt_len: Optional[int] = None
        self._prefill = None            # compiled at the first admission
        self.prefill_compiles = 0

    def submit(self, req: Request):
        # fixed prompt length per engine instance (scalar cache index);
        # production variant: per-slot index vector + length masking
        if self.prompt_len is None:
            self.prompt_len = len(req.prompt)
        assert len(req.prompt) == self.prompt_len, \
            "engine instance serves fixed-length prompts"
        req.t_queued = time.perf_counter()
        self.queue.append(req)

    # --------------------------------------------------------------
    def _prefill_slot(self, slot: int, req: Request):
        """Single-request prefill into the shared cache (slot-batched), by
        the engine's compiled prefill; the prompt's last position gives the
        request's first token."""
        req.t_admit = time.perf_counter()
        with TraceAnnotation("serve.admit", rid=req.rid, slot=slot):
            with TraceAnnotation("serve.prefill"):
                batch = {"tokens": np.asarray(req.prompt, np.int32)[None]}
                if self._prefill is None:
                    # params an argument: captured, they would be constants
                    self._prefill = jax.jit(make_prefill_step(
                        self.cfg, max_seq=self.max_seq)).lower(
                            self.params, batch).compile()
                    self.prefill_compiles += 1
                next_tok, cache1 = self._prefill(self.params, batch)
            with TraceAnnotation("serve.first_token"):
                first = int(np.asarray(next_tok)[0, 0])
            with TraceAnnotation("serve.splice"):
                self.cache = self._splice(self.cache, cache1, slot)
                self.tokens = self.tokens.at[slot, 0].set(first)
            self._emit(slot, req, first)

    def _emit(self, slot: int, req: Request, tok: int):
        if not req.out:
            req.t_first = time.perf_counter()
        req.out.append(tok)
        if len(req.out) >= req.max_new:
            req.done = True
            self.active[slot] = None
            self.finished.append(req)

    def step(self):
        """One engine tick: refill empty slots, run one decode step."""
        with TraceAnnotation("serve.step"):
            for i in range(self.slots):
                if self.active[i] is None and self.queue:
                    req = self.queue.pop(0)
                    self.active[i] = req
                    self._prefill_slot(i, req)
            if all(r is None for r in self.active):
                return False
            with TraceAnnotation("serve.decode"):
                next_tok, self.cache, *routed = self._decode(
                    self.params, self.cache, self.tokens)
            t0 = time.perf_counter()
            with TraceAnnotation("serve.decode_wait"):
                next_tok.block_until_ready()
            self.straggler.observe(time.perf_counter() - t0)
            self.tokens = next_tok
            self.steps += 1
            with TraceAnnotation("serve.fetch"):
                if routed:
                    toks, counts = jax.device_get((next_tok, routed[0]))
                    self.routed.append(counts)
                    toks = toks[:, 0]
                else:
                    toks = np.asarray(next_tok)[:, 0]
            for i, req in enumerate(self.active):
                if req is not None:
                    self._emit(i, req, int(toks[i]))
            return True

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Serve until the queue drains; returns the requests finished since
        the last ``run()``, in the order they finished."""
        self.routed.clear()
        while (self.queue or any(self.active)) and self.steps < max_steps:
            self.step()
        done, self.finished = self.finished, []
        return done
