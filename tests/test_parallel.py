"""Sharding-rule coverage on the FULL assigned configs (no compilation:
eval_shape + spec arithmetic) and elastic checkpoint resharding."""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest
pytest.importorskip("hypothesis")  # optional dev dep: degrade, don't die
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.models import api
from repro.parallel import sharding as shd

ROOT = Path(__file__).resolve().parent.parent


class FakeMesh:
    """Duck-typed mesh: sharding rules only read .shape and .axis_names."""
    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


MESH = FakeMesh(data=16, model=16)
MESH_POD = FakeMesh(pod=2, data=16, model=16)


def _axis_product(mesh, entry):
    axes = entry if isinstance(entry, tuple) else (entry,)
    prod = 1
    for a in axes:
        prod *= mesh.shape[a]
    return prod


@pytest.mark.parametrize("mesh", [MESH, MESH_POD], ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("arch", list(registry.ARCHS))
def test_param_specs_divide_full_configs(arch, mesh):
    """Every full-size assigned config gets valid (divisible) PartitionSpecs
    on both production meshes — the invariant the dry-run relies on."""
    cfg = registry.get(arch)
    struct = jax.eval_shape(lambda: api.init(cfg, jax.random.PRNGKey(0)))
    specs = shd.param_specs(cfg, struct, mesh)

    def check(path, leaf, spec):
        assert isinstance(spec, P)
        assert len(spec) <= len(leaf.shape)
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            prod = _axis_product(mesh, entry)
            assert leaf.shape[i] % prod == 0, (
                f"{arch}: {jax.tree_util.keystr(path)} dim{i}="
                f"{leaf.shape[i]} not divisible by {entry}={prod}")

    jax.tree_util.tree_map_with_path(
        check, struct, specs,
        is_leaf=lambda x: isinstance(x, P) or hasattr(x, "shape"))


def test_params_are_fsdp_sharded_not_replicated():
    """>=90% of parameter BYTES must shard over the fsdp axes for the big
    models (otherwise per-chip memory explodes silently)."""
    for arch in ("grok-1-314b", "command-r-plus-104b", "dbrx-132b"):
        cfg = registry.get(arch)
        struct = jax.eval_shape(lambda c=cfg: api.init(c, jax.random.PRNGKey(0)))
        specs = shd.param_specs(cfg, struct, MESH)
        tot, sharded = 0, 0
        for leaf, spec in zip(jax.tree.leaves(struct),
                              jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
            b = leaf.size
            tot += b
            entries = [e for e in spec if e is not None]
            flat = [a for e in entries for a in (e if isinstance(e, tuple) else (e,))]
            if "data" in flat:
                sharded += b
        assert sharded / tot > 0.9, f"{arch}: only {sharded/tot:.0%} FSDP-sharded"


@settings(max_examples=80, deadline=None)
@given(dim=st.integers(1, 10_000), ax=st.sampled_from(
    [("data",), ("model",), ("data", "model"), None]))
def test_sanitize_spec_always_valid(dim, ax):
    spec = P(ax if ax is None or len(ax) > 1 else ax[0])
    out = shd.sanitize_spec(spec, (dim,), MESH)
    entry = out[0] if len(out) else None
    if entry is not None:
        assert dim % _axis_product(MESH, entry) == 0


def test_elastic_reshard_restore(tmp_path):
    """FT contract: a checkpoint written under one mesh restores onto a
    DIFFERENT mesh layout with identical values (elastic scale-up/down)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt.manager import CheckpointManager
        from repro.launch.mesh import make_mesh

        out = sys.argv[1]
        w = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)

        mesh_a = make_mesh((2, 4), ("data", "model"))
        wa = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
        mgr = CheckpointManager(out, async_save=False)
        mgr.save(1, {"w": wa})

        # restore onto a re-shaped mesh (4x2) with transposed layout
        mesh_b = make_mesh((4, 2), ("data", "model"))
        sh_b = {"w": NamedSharding(mesh_b, P("model", "data"))}
        restored = mgr.restore(1, {"w": wa}, shardings=sh_b)
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
        assert restored["w"].sharding.is_equivalent_to(sh_b["w"], 2)
        print("ELASTIC_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path / "ck")],
        cwd=ROOT, timeout=300, capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "HOME": "/root"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ELASTIC_OK" in r.stdout
