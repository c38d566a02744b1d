"""A whole run of each cell's runner at tiny size on the CPU, past the
device gate: sound, it reads ``correct``; with the timed path broken
underneath, where a token or an answer is produced, it does not."""
from unittest import mock

import jax.numpy as jnp
from conftest import runner, tiny_serve


def result(cell, peak, seed=2**31 + 17):
    import run as run_mod
    res = runner(cell).run(cell, seed=seed, seconds=1.5, trace=False,
                           peak=peak, t_start=0.0)
    return run_mod.result_line(cell, res, {"platform": "cpu"}, False)


def test_serve_sound_run_is_correct(peak):
    line = result(tiny_serve(), peak)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 15 and line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"


def test_serve_altered_token_is_caught(peak):
    from repro.serving import kv
    # the least likely token in place of the greedy one, where it is made
    with mock.patch.object(kv, "greedy", lambda logits: jnp.argmin(
            logits, axis=-1).astype(jnp.int32)):
        line = result(tiny_serve(), peak)
    assert not line["correct"]
    gap = line["checks"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"]
