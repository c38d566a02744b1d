"""Helpers the tests import: polling instead of fixed sleeps, the repo
root, ``chip_smoke.py`` loaded as a module, and the serving LM's specs."""
import importlib.util
import pathlib
import sys

from repro.serving import LMSpec

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



#: tiny ``LMSpec``s, one for each shape of model the serving LM can
#: express: every spec here gets every check of ``test_lm_step.py`` and
#: ``test_lm_flow.py``.  Windows are 8 positions, so the prompts and
#: generations of those tests run past them.
_TINY = dict(vocab=64, head_dim=8, max_len=64)
_EXPERTS = dict(n_experts=8, top_k=2, expert_width=16)
_MIXED = (8, 8, 8, None)                # windowed:full at 3:1
SPECS = (
    LMSpec(n_heads=4, n_kv_heads=4, n_layers=2, **_TINY),     # dense MHA
    LMSpec(n_heads=4, n_kv_heads=2, n_layers=2, **_TINY),     # GQA 2:1
    LMSpec(n_heads=4, n_kv_heads=1, n_layers=2, **_TINY),     # MQA
    LMSpec(n_heads=4, n_kv_heads=2, n_layers=2, d_model=24,   # d != H*hd
           **_TINY),
    LMSpec(n_heads=4, n_kv_heads=2, n_layers=2, windows=(8, 8), **_TINY),
    LMSpec(n_heads=4, n_kv_heads=2, n_layers=4, windows=_MIXED, **_TINY),
    LMSpec(n_heads=4, n_kv_heads=2, n_layers=2, **_EXPERTS, **_TINY),
    LMSpec(n_heads=4, n_kv_heads=2, n_layers=4, d_model=40,   # Mellum2's
           windows=_MIXED, **_EXPERTS, **_TINY),
    LMSpec(n_heads=4, n_kv_heads=2, n_layers=2, n_experts=4, top_k=1,
           expert_width=16, **_TINY),
    LMSpec(n_heads=4, n_kv_heads=2, n_layers=2, n_experts=4, top_k=4,
           expert_width=16, **_TINY),
)


def spec_id(spec: LMSpec) -> str:
    """A test id naming what sets ``spec`` apart: heads, residual width,
    each layer's window (``f``: full) and the experts."""
    windows = "-".join("f" if w is None else str(w) for w in spec.windows)
    experts = (f"e{spec.n_experts}k{spec.top_k}" if spec.n_experts
               else "dense")
    return (f"h{spec.n_heads}kv{spec.n_kv_heads}-d{spec.d_model}-"
            f"w{windows}-{experts}")
