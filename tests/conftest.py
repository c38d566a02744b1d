"""Shared test configuration: CPU platform, per-test ceilings, JAX compile
cache.

* The suite runs on the CPU (``JAX_PLATFORMS=cpu`` unless set), with
  Pallas kernels interpreted: it never takes an accelerator.  The chip is
  reached only through ``chip_smoke.py``.

* Every test runs under a wall-clock ceiling (default 120 s) enforced with a
  SIGALRM watchdog, so a hung dataflow fails fast instead of wedging CI.
  Override per test with ``@pytest.mark.timeout(seconds)`` — the marker is
  compatible with pytest-timeout, which takes over transparently when
  installed (we then skip the built-in watchdog).
* The JAX persistent compilation cache is enabled
  (``repro.launch.use_compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` or the
  checkout's ``.jax_cache/``): the model/kernel smoke tests are dominated
  by XLA compilation, so warm reruns and cached CI runs cut minutes.

Helpers the tests import live in ``testkit.py``, not here: the benchmark's
own tests (``bench/tests``) are collected in the same session with a
``conftest`` of their own, so ``from conftest import ...`` would be
ambiguous.
"""
import math
import os
import signal
import threading

import pytest


# -- CPU platform + JAX persistent compilation cache (before jax imports) ----
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
from repro.launch import use_compile_cache  # noqa: E402

use_compile_cache()

DEFAULT_TIMEOUT_S = 120.0

try:
    import pytest_timeout  # noqa: F401
    _HAVE_PYTEST_TIMEOUT = True
except ImportError:
    _HAVE_PYTEST_TIMEOUT = False


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timeout(seconds): per-test wall-clock ceiling (watchdog)")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """SIGALRM-based per-test ceiling (pytest-timeout fallback).

    Only active on the main thread of a POSIX process; elsewhere (or when
    the real pytest-timeout plugin is installed) it steps aside.
    """
    marker = item.get_closest_marker("timeout")
    seconds = float(marker.args[0]) if marker and marker.args \
        else DEFAULT_TIMEOUT_S
    usable = (not _HAVE_PYTEST_TIMEOUT
              and hasattr(signal, "SIGALRM")
              and threading.current_thread() is threading.main_thread()
              and seconds > 0)
    if not usable:
        yield
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded the {seconds:.0f}s per-test ceiling")

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(max(1, math.ceil(seconds)))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)
