"""``chip_smoke.py`` phases at reduced width on the CPU (kernels in
interpret mode), the script's refusal to run off a TPU, the sharded
training launcher on four virtual CPU devices, and where the compile cache
goes."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import registry
from repro.launch import serve as serve_launcher
from repro.utils import compile_cache

ROOT = Path(__file__).resolve().parent.parent


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def _run(args, cwd=ROOT, timeout=600, **env):
    return subprocess.run([sys.executable] + args, cwd=cwd, timeout=timeout,
                          capture_output=True, text=True, env=_env(**env))


FOUR_CPUS = "--xla_force_host_platform_device_count=4"


def test_kernels_match_reference_interpret():
    cfg = registry.get("qwen2.5-3b").reduced()
    errs = cs.check_kernels(cfg, L=256, B=2, S=512, cache_len=300,
                            interpret=True)
    assert set(errs) == {"flash_attention/default",
                         "flash_attention/autotuned", "flash_decode"}


def test_serve_matches_cache_free_forward():
    served = serve_launcher.serve(serve_launcher.parse_args(
        ["--reduced", "--requests", "6", "--slots", "4", "--prompt-len",
         "16", "--max-new", "6", "--max-seq", "32"]))
    assert [r.rid for r in sorted(served.finished, key=lambda r: r.rid)] \
        == list(range(6))
    res = cs.check_served(served, n_check=2)
    assert res["positions"] == 12


def test_check_served_catches_wrong_tokens():
    served = serve_launcher.serve(serve_launcher.parse_args(
        ["--reduced", "--requests", "2", "--slots", "2", "--prompt-len",
         "16", "--max-new", "6", "--max-seq", "32"]))
    for r in served.finished:
        r.out = [(t + 1) % served.cfg.vocab_size for t in r.out]
    with pytest.raises(AssertionError, match="disagree"):
        cs.check_served(served)


def test_main_refuses_cpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "platform cpu" in r.stdout
    assert '"ok"' not in r.stdout


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run(["chip_smoke.py"], cwd=tmp_path, PYTHONPATH="")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_import_leaves_the_process_one_device_view():
    """The smoke holds the chip in one process: it must not pull in the
    dry-run, whose ``main()`` rewrites XLA_FLAGS, or the sweep, which
    forks workers."""
    script = ("import sys, chip_smoke; "
              "print(sorted(m for m in ('repro.launch.dryrun', "
              "'repro.analysis.sweep') if m in sys.modules))")
    r = _run(["-c", script], PYTHONPATH=f"{ROOT}:{ROOT / 'src'}")
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip() == "[]"


def test_train_launcher_2x2_on_four_cpu_devices():
    r = _run(["-m", "repro.launch.train", "--reduced", "--dp", "2", "--tp",
              "2", "--batch", "4", "--seq", "32", "--steps", "2",
              "--ckpt-every", "0"], XLA_FLAGS=FOUR_CPUS)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "mesh {'data': 2, 'model': 2}" in r.stdout
    assert "done: 2 steps" in r.stdout


def test_training_meshes_agree_reduced():
    script = ("import chip_smoke as cs; "
              "r = cs.check_training(['--seq', '64'], reduced=True); "
              "print('REL', r['step1_rel'])")
    r = _run(["-c", script], XLA_FLAGS=FOUR_CPUS,
             PYTHONPATH=f"{ROOT}:{ROOT / 'src'}")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "mesh {'data': 1, 'model': 4}" in r.stdout
    assert "REL" in r.stdout


def test_compile_cache_honours_env(tmp_path):
    script = ("import jax, jax.numpy as jnp; "
              "from repro.utils.compile_cache import use_compile_cache; "
              "print(use_compile_cache()); "
              "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()")
    r = _run(["-c", script], JAX_COMPILATION_CACHE_DIR=str(tmp_path),
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
