"""No silent fallbacks: a kernel that cannot lower raises, no TPU means no
result, and the compile cache goes where the environment says."""

import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

from tpu_scheduler.backends.native import NativeBackend  # noqa: E402
from tpu_scheduler.errors import CompileFailed  # noqa: E402
from tpu_scheduler.backends.tpu import TpuBackend  # noqa: E402
from tpu_scheduler.ops.pack import pack_snapshot  # noqa: E402
from tpu_scheduler.parallel.mesh import make_mesh  # noqa: E402
from tpu_scheduler.parallel.sharded import ShardedBackend  # noqa: E402
from tpu_scheduler.runtime.controller import Scheduler  # noqa: E402
from tpu_scheduler.runtime.fake_api import FakeApiServer  # noqa: E402
from tpu_scheduler.testing import synth_cluster  # noqa: E402
from tpu_scheduler.utils import compile_cache  # noqa: E402
from tpu_scheduler.utils.compile_cache import DEFAULT_CACHE_DIR, enable_compilation_cache  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("kind", ["tpu", "tpu-sharded"])
def test_kernel_lowering_failure_raises(kind):
    """Compiled Mosaic asked for on the CPU cannot lower.  The backend
    raises the lowering error — no jnp retry, no BackendUnavailable — and
    the controller does not swap in its native fallback either."""

    def backend():
        return TpuBackend(use_pallas=True) if kind == "tpu" else ShardedBackend(make_mesh(tp=2), use_pallas=True)

    snap = synth_cluster(n_nodes=8, n_pending=16, n_bound=4, seed=1)
    with pytest.raises(ValueError, match="interpret"):
        backend().schedule(pack_snapshot(snap))
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, backend(), fallback_backend=NativeBackend())
    try:
        with pytest.raises(ValueError, match="interpret"):
            sched.run_cycle()
    finally:
        sched.close()
    assert sched.metrics.snapshot().get("scheduler_backend_fallbacks_total", 0) == 0


def _run_with_fallback(kind, snap):
    """One controller cycle with the native fallback configured; returns
    (raised exception or None, scheduler_backend_fallbacks_total)."""
    backend = ShardedBackend(make_mesh(tp=2), use_pallas=False) if kind == "tpu-sharded" else TpuBackend(use_pallas=False)
    api = FakeApiServer()
    api.load(snap.nodes, snap.pods)
    sched = Scheduler(api, backend, fallback_backend=NativeBackend())
    try:
        try:
            sched.run_cycle()
            err = None
        except Exception as e:  # noqa: BLE001 — the test inspects it
            err = e
    finally:
        sched.close()
    return err, sched.metrics.snapshot().get("scheduler_backend_fallbacks_total", 0)


@pytest.mark.parametrize("kind", ["tpu", "tpu-sharded"])
def test_compile_failure_raises_and_execute_fault_falls_back(kind, monkeypatch):
    """A program the compiler refuses (a Mosaic rejection arrives as
    JaxRuntimeError from compile) raises CompileFailed through the
    controller with the native fallback configured, and no fallback is
    counted.  A JaxRuntimeError from running the compiled program keeps the
    BackendUnavailable -> fallback contract."""
    snap = synth_cluster(n_nodes=8, n_pending=16, n_bound=4, seed=2)

    def refuse(self):
        raise jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(compile_cache, "_EXECUTABLES", {})
    with monkeypatch.context() as m:
        m.setattr(jax.stages.Lowered, "compile", refuse)
        err, fallbacks = _run_with_fallback(kind, snap)
    assert isinstance(err, CompileFailed) and "Mosaic" in str(err), err
    assert fallbacks == 0

    def fault(self, *args, **kwargs):
        raise jax.errors.JaxRuntimeError("INTERNAL: device lost")

    with monkeypatch.context() as m:
        m.setattr(jax.stages.Compiled, "__call__", fault)
        err, fallbacks = _run_with_fallback(kind, snap)
    assert err is None
    assert fallbacks == 1


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_no_tpu_exits_without_result(script):
    """Held to the CPU, neither entry point prints a result: non-zero exit,
    no ``"ok": true`` line and no metric line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, script)], capture_output=True, text=True, timeout=300, cwd=REPO, env=env
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout and '"metric"' not in out.stdout, out.stdout
    assert "needs a TPU" in out.stderr


def test_compile_cache_honours_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, code sets no directory and the
    process caches there and nowhere else."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from tpu_scheduler.utils.compile_cache import enable_compilation_cache\n"
        "print(enable_compilation_cache(), jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n"
    )
    cache, home = tmp_path / "cache", tmp_path / "home"
    home.mkdir()
    env = dict(
        os.environ,
        HOME=str(home),
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=REPO,
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    # The configured directory is the one jax caches in; the old ~/.cache
    # default is gone.
    assert out.stdout.split() == [str(cache), str(cache)]
    assert os.listdir(cache), "nothing was cached in JAX_COMPILATION_CACHE_DIR"
    assert not os.listdir(home)


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compilation_cache() == DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
        assert os.path.isdir(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
