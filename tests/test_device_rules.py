"""The device rules: no silent interpreter or CPU fallback, the chip stays
with one process, a compile cache that can be placed from outside.

* ``kernels.ops.pallas_interpret`` is asked when a pellet or flow is
  built: compiled kernels on a TPU, the interpreter on the CPU, an error
  anywhere else.
* Importing the package initialises no JAX backend.
* A process-backed worker pins its JAX to the CPU before its first JAX
  call, whatever platform its parent's environment names.
* ``launch.use_compile_cache`` leaves a set ``JAX_COMPILATION_CACHE_DIR``
  alone and otherwise points at ``.jax_cache/`` in the checkout.
* ``chip_smoke.py`` refuses to run off the chip, and its two phases pass
  at a tiny size on the CPU with interpreted kernels.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest
from testkit import ROOT, load_chip_smoke

from repro.cluster.workers.handle import WorkerHandle
from repro.kernels import ops
from repro.launch import use_compile_cache
from repro.serving import LMSpec


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False)])
def test_pallas_interpret_follows_platform(monkeypatch, platform, interpret):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    assert ops.pallas_interpret() is interpret


@pytest.mark.parametrize("platform", ["gpu", "rocm", "METAL"])
def test_pallas_interpret_refuses_other_platforms(monkeypatch, platform):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    with pytest.raises(RuntimeError, match=repr(platform)):
        ops.pallas_interpret()


_IMPORT_ALL = """
import importlib, pathlib, sys
from jax._src import xla_bridge
src = pathlib.Path(sys.argv[1])
for path in sorted(src.joinpath("repro").rglob("*.py")):
    parts = path.relative_to(src).with_suffix("").parts
    if parts[-1] == "__main__" or parts[-2:] == ("launch", "dryrun"):
        continue
    name = ".".join(parts[:-1] if parts[-1] == "__init__" else parts)
    importlib.import_module(name)
    if xla_bridge._backends:
        sys.exit(f"importing {name} initialised {sorted(xla_bridge._backends)}")
"""


def test_importing_the_package_initialises_no_backend():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", _IMPORT_ALL,
                           str(ROOT / "src")], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- process workers stay off the chip --------------------------------------

def _backend_of(_):
    return jax.default_backend()


def _make_backend_probe():
    from repro import FnPellet
    return FnPellet(_backend_of)


def test_process_worker_pins_jax_to_cpu(monkeypatch):
    """The child inherits a platform no process could open: only the
    worker's own pin lets it compute with JAX at all."""
    monkeypatch.setenv("JAX_PLATFORMS", "no_such_platform")
    handle = WorkerHandle("probe", ring_bytes=1 << 16)
    try:
        handle.wait_ready(60)
        handle.register("probe", _make_backend_probe)
        rows, note = handle.compute_rows("probe", [0])
    finally:
        handle.shutdown()
    assert rows == [("ok", "cpu")], (rows, note)


# -- compile cache placement ------------------------------------------------

@pytest.fixture
def cache_dir_config():
    prior = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prior)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                                cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert use_compile_cache() == want == use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == want


# -- chip_smoke.py -----------------------------------------------------------

@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_off_the_chip(tmp_path, where):
    """Under ``JAX_PLATFORMS=cpu`` the gate names the platform; copied
    into a directory without the repo, the script cannot import it.
    Either way: a non-zero exit and no result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if where == "checkout":
        assert "'cpu'" in proc.stderr


def test_chip_smoke_phases_at_tiny_size_on_cpu():
    cs = load_chip_smoke()
    spec = LMSpec(vocab=64, n_heads=4, n_kv_heads=2, head_dim=8,
                  n_layers=2, max_len=32)
    serving = cs.serving_phase(spec, n_slots=4, max_prompt=8, n_requests=6,
                               prompt_lo=2, budget=5, interpret=True)
    assert len(serving["passes"]) == 2
    assert serving["prefill_err"] <= 1e-5 and serving["decode_err"] <= 1e-5
    assert serving["prefill_agree"] == serving["decode_agree"] == 1.0
    stream = cs.stream_phase(k=16, dim=32, windows=2, rows=300,
                             interpret=True)
    assert len(stream["windows"]) == 2
    json.dumps(stream)      # plain numbers, printable as a result
