"""CPU tests of the benchmark's yardstick, at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The cells' runners are driven here without the chip: the tests call
``run`` directly, past ``bench/run.py``'s device gate."""
import copy
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import pytest  # noqa: E402

from yard import common  # noqa: E402

#: CPU stand-ins for the chip's peaks (the readers need a table entry)
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def tiny_serve(cell_name: str = "serve-qwen3-1.7b-poisson") -> common.Cell:
    """The serving cell at a size the CPU runs in seconds: every width
    shrunk, the traffic's shape kept."""
    cell = common.load_cell(cell_name)
    cfg = copy.deepcopy(cell.config)
    cfg.update(vocab_size=64, hidden_size=32, num_attention_heads=4,
               num_key_value_heads=2, head_dim=8, num_hidden_layers=2,
               intermediate_size=64)
    cfg["serve"].update(slots=4, max_len=64, max_prompt=24)
    cfg["check"].update(sample_tokens=40, batch=2)
    traffic = copy.deepcopy(cell.traffic)
    traffic["arrivals"]["rate_per_s"] = 10.0
    traffic["prompt"].update(median=8, min=4, max=24)
    traffic["budget"].update(median=4, min=2, max=8)
    cell.config, cell.traffic = cfg, traffic
    return cell


def runner(cell: common.Cell):
    kind = cell.config["kind"]
    return common.load_module(BENCH / "kinds" / f"{kind}.py",
                              f"bench_kind_{kind}")


@pytest.fixture
def peak():
    return dict(PEAK)
