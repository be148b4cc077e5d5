"""Closed loop of clients on the program's serving engine.

``clients`` clients each send a request (``prompt_len`` token ids drawn
uniformly from the vocabulary, ``max_new`` greedy tokens) and send their
next one when its last token has come back. The harness calls
``ServeEngine.step()`` and nothing else of the engine's loop; a client
sees a token when the ``step()`` that made it returns.

Set-up builds the weights on the device from the seed, builds the engine,
and serves one request of the cell's prompt length through it (one
prefill, one decode step), so that the window compiles nothing the
harness could have compiled before. The window then runs for ``seconds``;
once it closes the clients send nothing more, and the engine steps on until
every request sent in the window has finished (a long answer can outlast
the window), for at most ``DRAIN_S``. A request that has not finished by
then counts as failed. Once the device's memory peak is read, the engine
and its weights are freed and a sample of the finished
requests is compared with the float32 reference (``chipbench/reference``):
``check_per_slot`` requests from each slot, drawn from the seed, and the
longest of all, so that a fault confined to one slot is in every sample.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import monitoring

from chipbench import harness, trace
from chipbench.reference import dense_lm

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DRAIN_S = 150.0

# Configuration file keys -> the program's ModelConfig fields.
_SIZES = {"hidden_size": "d_model", "num_hidden_layers": "num_layers",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
          "norm": "norm", "qkv_bias": "qkv_bias", "dtype": "compute_dtype"}


def program_config(conf):
    """The program's ModelConfig for ``conf``: its registry entry with
    every size the configuration file states."""
    from repro.configs import registry
    return dataclasses.replace(
        registry.get(conf["program_arch"]),
        **{f: conf[k] for k, f in _SIZES.items() if k in conf})


def prompt(seed: int, client: int, k: int, n: int, vocab: int) -> np.ndarray:
    """Client ``client``'s ``k``-th prompt: the same for the same seed."""
    return np.random.default_rng([seed, client, k]).integers(
        0, vocab, n, dtype=np.int32)


@dataclass
class Sent:
    req: object                       # the engine's Request
    client: int
    k: int
    t_submit: float
    times: List[float] = field(default_factory=list)   # per token
    slot: Optional[int] = None        # the engine's slot that served it


@dataclass
class Step:
    t0: float
    t1: float
    admitted: int                     # requests prefilled in this step
    contexts: List[int]               # per decode token: positions attended


class Loop:
    """The clients and the engine, stepped by the harness."""

    def __init__(self, engine, traffic, seed, vocab):
        self.eng, self.tr, self.seed, self.vocab = engine, traffic, seed, \
            vocab
        self.sent: List[Sent] = []
        self.steps: List[Step] = []
        self.open = True              # clients still send
        self.window_steps = 0         # steps started in the window
        self.unplaced: List[Sent] = []    # sent, slot not yet seen

    def send(self, client: int, k: int, t: float) -> None:
        from repro.serve.engine import Request
        req = Request(rid=len(self.sent),
                      prompt=prompt(self.seed, client, k,
                                    self.tr["prompt_len"], self.vocab),
                      max_new=self.tr["max_new"])
        self.sent.append(Sent(req, client, k, t))
        self.unplaced.append(self.sent[-1])
        self.eng.submit(req)

    def step(self) -> Step:
        live = [s for s in self.sent if not s.req.done]
        before = [len(s.req.out) for s in live]
        queued = len(self.eng.queue)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.step"):
            self.eng.step()
        t1 = time.perf_counter()
        admitted = queued - len(self.eng.queue)
        if admitted:
            slot_of = {id(r): i for i, r in enumerate(self.eng.active)
                       if r is not None}
            for s in self.unplaced:
                s.slot = slot_of.get(id(s.req))
            self.unplaced = [s for s in self.unplaced if s.slot is None
                             and not s.req.done]
        P = self.tr["prompt_len"]
        contexts = []
        for s, n0 in zip(live, before):
            n1 = len(s.req.out)
            s.times.extend([t1] * (n1 - n0))
            if n1 > max(n0, 1):                  # a decode token came
                contexts.append(P + n1 - 1)
            if s.req.done and n1 > n0 and self.open:
                self.send(s.client, s.k + 1, t1)
        st = Step(t0, t1, admitted, contexts)
        self.steps.append(st)
        return st


def build(cell, seed: int):
    """Weights, engine and one warm request through it."""
    from repro.launch.serve import init_params
    from repro.serve.engine import Request, ServeEngine
    cfg = program_config(cell.config)
    tr = cell.traffic
    params = init_params(cfg, seed)
    eng = ServeEngine(cfg, params, slots=tr["slots"], max_seq=tr["max_seq"])
    warm = Request(rid=-1, prompt=prompt(seed, tr["clients"], 0,
                                         tr["prompt_len"], cfg.vocab_size),
                   max_new=2)
    eng.submit(warm)
    while not warm.done:
        eng.step()
    eng.finished.clear()
    return eng


def serve(eng, traffic, seed, seconds, *, compiles=None):
    """The window: every client sends at its start; the loop steps until
    ``seconds`` have passed, and then the clients stop sending. Returns
    (loop, t_start, t_end)."""
    loop = Loop(eng, traffic, seed, eng.cfg.vocab_size)

    def count(event, secs, **_):
        if event == COMPILE_EVENT and compiles is not None:
            compiles.append(time.perf_counter())
    monitoring.register_event_duration_secs_listener(count)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            t_start = time.perf_counter()
            t_end = t_start + seconds
            for c in range(traffic["clients"]):
                loop.send(c, 0, t_start)
            while time.perf_counter() < t_end:
                loop.step()
            loop.window_steps = len(loop.steps)
    finally:
        monitoring.unregister_event_duration_listener(count)
    loop.open = False
    return loop, t_start, t_end


def drain(loop: Loop, limit_s: float = DRAIN_S) -> None:
    """Steps until every request sent has finished, or ``limit_s`` have
    passed."""
    give_up = time.perf_counter() + limit_s
    while any(not s.req.done for s in loop.sent) \
            and time.perf_counter() < give_up:
        loop.step()


def sample(loop: Loop, seed: int, per_slot: int) -> List[Sent]:
    """The finished requests to check: the longest of all, then
    ``per_slot`` of each slot's, drawn from the seed."""
    done = [s for s in loop.sent if s.req.done]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.req.out))
    by_slot = {}
    for s in done:
        if s is not longest:
            by_slot.setdefault(s.slot, []).append(s)
    rng = np.random.default_rng([seed, 1])
    picked = [longest]
    for slot in sorted(by_slot, key=lambda k: (k is None, k)):
        group = by_slot[slot]
        n = max(per_slot - (longest.slot == slot), 0)
        pick = rng.choice(len(group), size=min(n, len(group)), replace=False)
        picked += [group[i] for i in sorted(pick)]
    return picked


def reference_gaps(conf, seed, picked: List[Sent], block: int,
                   control=None):
    """Per sampled request, the gap by which each served token's reference
    logit lies below the reference's best at its position. With
    ``control`` (a reference ``linear``), also the gap of the token the
    control puts first. Returns (served gaps, control gaps or None)."""
    weights = dense_lm.init_weights(conf, seed)
    gaps, ctl = [], []
    for i in range(0, len(picked), block):
        chunk = picked[i:i + block]
        P = len(chunk[0].req.prompt)
        n = len(chunk[0].req.out)
        toks = np.stack([np.concatenate([s.req.prompt, s.req.out[:-1]])
                         for s in chunk]).astype(np.int32)
        served = np.stack([s.req.out for s in chunk]).astype(np.int32)
        pos = np.broadcast_to(np.arange(P - 1, P - 1 + n), served.shape)
        ref = dense_lm.logits(conf, weights, toks, pos)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, jnp.asarray(served)[..., None],
                                  axis=-1)[..., 0]
        gaps.append(np.asarray(best - got).ravel())
        if control is not None:
            low = dense_lm.logits(conf, weights, toks, pos, linear=control)
            pick = jnp.argmax(low, axis=-1)
            got = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
            ctl.append(np.asarray(best - got).ravel())
            del low
        del ref
    return (np.concatenate(gaps),
            np.concatenate(ctl) if control is not None else None)


def memory_peak() -> int:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(cell, *, seed: int, seconds: float, trace_dir: Optional[str] = None,
        t_process: Optional[float] = None) -> "harness.Run":
    tr = cell.traffic
    t_process = time.perf_counter() if t_process is None else t_process
    eng = build(cell, seed)
    jax.effects_barrier()
    if trace_dir is not None:
        trace.start(trace_dir)
    setup_s = time.perf_counter() - t_process
    compiles: List[float] = []
    try:
        loop, t_start, t_end = serve(eng, tr, seed, seconds,
                                     compiles=compiles)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    drain(loop)
    peak = memory_peak()
    eng.cache = eng.params = eng.tokens = None
    del eng
    gc.collect()

    sent = [s for s in loop.sent if s.t_submit <= t_end]
    failed = sum(1 for s in sent if not s.req.done)
    picked = sample(loop, seed, tr["check_per_slot"])
    checks = []
    limit = cell.limits["max_logit_gap"]
    gaps = np.zeros(0)
    if picked:
        gaps, _ = reference_gaps(cell.config, seed, picked, tr["check_block"])
        worst = float(gaps.max())
        checks.append(harness.Check("max_logit_gap", worst, limit,
                                    worst <= limit))
    # the sample has to be whole, every slot in it: a least, not a most
    least = tr["check_per_slot"] * tr["slots"] * tr["max_new"]
    checks.append(harness.Check("sampled_tokens", float(gaps.size),
                                float(least), gaps.size >= least))
    checks.append(harness.Check("failed_requests", float(failed), 0.0,
                                failed == 0))
    return harness.Run(
        cell=cell, seconds=seconds, setup_s=setup_s, attempted=len(sent),
        failed=failed, checks=checks, memory_peak_bytes=peak,
        records={"sent": sent, "t_start": t_start, "t_end": t_end,
                 "compiles": compiles,
                 "window_steps": loop.steps[:loop.window_steps]})
