"""Helpers the tests import: polling instead of fixed sleeps, the repo
root, and ``chip_smoke.py`` loaded as a module."""
import importlib.util
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def wait_until(predicate, *, timeout: float = 10.0,
               interval: float = 0.005) -> bool:
    """Poll ``predicate`` until truthy or ``timeout``; returns the verdict.

    Use instead of fixed ``time.sleep`` so tests advance the moment the
    engine reaches the awaited state.
    """
    import time
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return bool(predicate())


def load_chip_smoke():
    """The repo-root ``chip_smoke.py`` as a module (loaded once)."""
    if "chip_smoke" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", ROOT / "chip_smoke.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["chip_smoke"]
