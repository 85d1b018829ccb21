"""CPU checks of the chip smoke script, the compile-cache helper and the
APoZ kernel's grid order (tests/test_tpu_compile.py compiles the kernel
for the chip itself)."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.apoz import apoz_counts_pallas, column_block
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(admissions=400, medicines=64, hidden=(16, 8), clients=5,
            batch_size=16, local_epochs=1, loops=3)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses look it up there
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    size = smoke.Size(**TINY)
    return size, smoke.make_cohort(size)


def test_phases_pass_at_tiny_size(smoke, tiny):
    """Every one-chip phase runs and passes its checks on the CPU, and
    the fused and per-round runs agree exactly here (bit parity holds
    on the CPU; the chip gets PARAM_TOL)."""
    size, cohort = tiny
    phases = dict(smoke.SINGLE_CHIP_PHASES)
    ctx = {}
    out = {name: fn(cohort, size, ctx) for name, fn in phases.items()}
    assert out["fused_scbf"]["param_divergence"] == 0.0
    assert out["fused_scbf"]["fused_compiles"] <= 2
    assert out["fused_scbfwp"]["prune_steps"] >= 2
    assert set(out["apoz_kernel"]) == {"2048x256", "2048x512", "2048x64"}
    for name in ("scbf", "fedavg", "fused_scbf", "fused_scbfwp"):
        assert out[name]["auc_roc"] > 0.5
        assert out[name]["first_call_s"] > 0 and out[name]["steady_s"] > 0


def test_pod_phase_on_one_device(smoke, tiny):
    """The pod phase's comparison logic, with the one device there is."""
    size, cohort = tiny
    out = smoke.phase_pods(cohort, size, {}, 1)
    assert out["param_divergence"] == 0.0


def test_a_failed_check_fails_its_phase(smoke, tiny, capsys):
    size, cohort = tiny

    def broken(c, s, x):
        smoke.require(False, "deliberate")

    assert not smoke.run_phases((("broken", broken),), cohort, size)
    assert "FAILED SmokeFailure: deliberate" in capsys.readouterr().err


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_repo", "script_alone"])
def test_script_fails_without_a_tpu(tmp_path, alone):
    """On the CPU (and with none of the repo beside it) the script exits
    non-zero and prints no ok line."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    if not alone:
        assert "no TPU" in out.stderr


@pytest.fixture
def restore_cache_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_honours_env_var(monkeypatch, tmp_path,
                                       restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cc")


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                   restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # same path on every call: never a temporary, pid- or time-based name
    assert compile_cache.enable_compile_cache() == path
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compiled_programs_land_in_the_cache_dir(tmp_path):
    """A program compiled with the cache on is written under the chosen
    directory (run in a child so this process's cache stays as it is)."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(tmp_path / "cc")
    assert any((tmp_path / "cc").iterdir())


@pytest.mark.parametrize("shape", [(1024, 512), (2048, 768), (512, 64)])
def test_apoz_kernel_counts_across_column_blocks(shape):
    """Interpret-mode counts equal the jnp reference with several column
    and batch blocks (the batch reduction is the last grid axis)."""
    b, n = shape
    a = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(b + n), shape))
    a = a.at[::3, ::5].set(0.0)
    got = apoz_counts_pallas(a, bb=256, bn=column_block(n), interpret=True)
    want = jnp.sum(a == 0.0, axis=0, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_column_block_rule():
    assert column_block(512) == 256
    assert column_block(64) == 64
    assert column_block(300) is None
