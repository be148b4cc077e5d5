"""Fault-tolerance substrate: checkpoint manager + trainer semantics +
serving engine + data-pipeline determinism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.manager import CheckpointManager
from repro.configs import registry
from repro.data.synthetic import DataIterator, token_batch
from repro.serve.engine import Request, ServeEngine
from repro.train import trainer
from repro.train.optimizer import OptConfig, schedule_lr

KEY = jax.random.PRNGKey(0)


def _cfg():
    return registry.get("olmo-1b").reduced()


def _run_cfg(**kw):
    return trainer.RunConfig(
        opt=OptConfig(lr=1e-3, warmup_steps=2, total_steps=50), **kw)


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------

def test_ckpt_roundtrip_and_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=2, async_save=False)
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    for step in (10, 20, 30):
        mgr.save(step, jax.tree.map(lambda x: x + step, tree))
    assert mgr.all_steps() == [20, 30]          # retention pruned step 10
    step, restored = mgr.restore_latest(tree)
    assert step == 30
    np.testing.assert_array_equal(restored["a"], np.arange(6).reshape(2, 3) + 30)


def test_ckpt_async_save_publishes_atomically(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=3, async_save=True)
    tree = {"w": jnp.ones((64, 64))}
    mgr.save(1, tree)
    mgr.wait()
    assert (tmp_path / "step_1" / "manifest.json").exists()
    assert not list(tmp_path.glob("*.tmp"))


def test_ckpt_restore_validates_structure(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False)
    mgr.save(1, {"w": jnp.ones(3)})
    with pytest.raises(ValueError):
        mgr.restore(1, {"w": jnp.ones(3), "extra": jnp.ones(1)})
    with pytest.raises(ValueError):
        mgr.restore(1, {"w": jnp.ones(5)})


def test_ckpt_restore_to_new_sharding(tmp_path):
    """Elastic restore: same bytes, different target placement."""
    mgr = CheckpointManager(tmp_path, async_save=False)
    src = {"w": jnp.arange(8.0)}
    mgr.save(2, src)
    shard = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    out = mgr.restore(2, src, shardings={"w": shard})
    np.testing.assert_array_equal(out["w"], np.arange(8.0))


def test_train_resume_bit_identical(tmp_path):
    """ckpt+restart at step k must equal an uninterrupted run (state and
    data order) — the preemption-recovery contract."""
    cfg = _cfg()
    run = _run_cfg(microbatches=1, remat="none")
    step_fn = jax.jit(trainer.make_train_step(cfg, run))

    def batches(start):
        return DataIterator(cfg, batch=4, seq=16, start_step=start)

    # uninterrupted 6 steps
    s_a = trainer.init_state(cfg, run, KEY)
    it = batches(0)
    for _ in range(6):
        s_a, _ = step_fn(s_a, {k: jnp.asarray(v) for k, v in next(it).items()})

    # interrupted at 3 + resumed
    s_b = trainer.init_state(cfg, run, KEY)
    it = batches(0)
    for _ in range(3):
        s_b, _ = step_fn(s_b, {k: jnp.asarray(v) for k, v in next(it).items()})
    mgr = CheckpointManager(tmp_path, async_save=False)
    mgr.save(3, s_b)
    _, s_b2 = mgr.restore_latest(s_b)
    it2 = batches(3)                       # stateless data resume
    for _ in range(3):
        s_b2, _ = step_fn(s_b2, {k: jnp.asarray(v) for k, v in next(it2).items()})

    for a, b in zip(jax.tree.leaves(s_a.params), jax.tree.leaves(s_b2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# trainer semantics
# ---------------------------------------------------------------------------

def test_microbatch_equivalence():
    """4 microbatches produce the same loss and accumulated-gradient norm
    as 1 (first-step Adam updates are ill-conditioned near g~0, so the
    contract is on the gradients, not the post-Adam params)."""
    cfg = _cfg()
    batch = {k: jnp.asarray(v) for k, v in
             token_batch(cfg, batch=8, seq=16, step=0).items()}
    outs = {}
    for mb in (1, 4):
        run = _run_cfg(microbatches=mb, remat="none")
        state = trainer.init_state(cfg, run, KEY)
        step = jax.jit(trainer.make_train_step(cfg, run))
        new, m = step(state, batch)
        outs[mb] = (float(m["loss"]), float(m["grad_norm"]))
    assert outs[1][0] == pytest.approx(outs[4][0], rel=1e-5)
    assert outs[1][1] == pytest.approx(outs[4][1], rel=1e-3)


def test_int8_grad_compression_error_feedback():
    """Quantize->dequantize identity: deq + residual == input exactly, the
    residual feeds back, and over repeated steps the accumulated update of a
    constant gradient converges to the exact sum (the EF guarantee)."""
    from repro.train.trainer import _quantize_int8
    g = jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)), jnp.float32)
    err = jnp.zeros_like(g)
    deq, err2 = _quantize_int8(g, err)
    np.testing.assert_allclose(np.asarray(deq + err2), np.asarray(g),
                               rtol=1e-6, atol=1e-6)
    # EF convergence: sum of dequantized updates -> n * g
    err = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    n = 16
    for _ in range(n):
        deq, err = _quantize_int8(g, err)
        acc = acc + deq
    np.testing.assert_allclose(np.asarray(acc / n), np.asarray(g),
                               rtol=0, atol=float(jnp.max(jnp.abs(g))) / 127)

    # and the trainer wires it: state carries a nonzero residual
    cfg = _cfg()
    run = _run_cfg(microbatches=1, remat="none", grad_compress="int8")
    state = trainer.init_state(cfg, run, KEY)
    step = jax.jit(trainer.make_train_step(cfg, run))
    it = DataIterator(cfg, batch=4, seq=16)
    state, m = step(state, {k: jnp.asarray(v) for k, v in next(it).items()})
    ef_norm = sum(float(jnp.abs(e).sum()) for e in jax.tree.leaves(state.ef_error))
    assert ef_norm > 0


def test_remat_matches_no_remat():
    cfg = _cfg()
    batch = {k: jnp.asarray(v) for k, v in
             token_batch(cfg, batch=2, seq=16, step=0).items()}
    grads = {}
    for remat in ("none", "full"):
        run = _run_cfg(microbatches=1, remat=remat)
        loss_fn = trainer.make_loss_fn(cfg, run)
        state = trainer.init_state(cfg, run, KEY)
        (_, _), g = jax.value_and_grad(loss_fn, has_aux=True)(state.params, batch)
        grads[remat] = g
    for a, b in zip(jax.tree.leaves(grads["none"]), jax.tree.leaves(grads["full"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_wsd_schedule_shape():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100, schedule="wsd",
                    wsd_stable_frac=0.8, min_lr_frac=0.1)
    lrs = [float(schedule_lr(cfg, s)) for s in range(101)]
    assert lrs[5] == pytest.approx(0.5)               # warmup
    assert lrs[50] == pytest.approx(1.0)              # stable plateau
    assert lrs[100] == pytest.approx(0.1, abs=0.02)   # decayed to min
    assert all(a >= b - 1e-6 for a, b in zip(lrs[10:], lrs[11:]))  # monotone


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

def test_serve_engine_batched_requests():
    cfg = _cfg()
    from repro.models import api
    params = api.init(cfg, KEY)
    eng = ServeEngine(cfg, params, slots=2, max_seq=32)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 6),
                    max_new=3) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run(max_steps=200)
    assert sorted(r.rid for r in finished) == [r.rid for r in reqs]
    assert all(len(r.out) == 3 and r.done for r in reqs)
    assert eng.steps < 200


def test_data_pipeline_deterministic_and_sharded():
    cfg = _cfg()
    a = token_batch(cfg, batch=4, seq=32, step=7, seed=3)
    b = token_batch(cfg, batch=4, seq=32, step=7, seed=3)
    c = token_batch(cfg, batch=4, seq=32, step=8, seed=3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert a["tokens"].max() < cfg.vocab_size
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
