"""The serving LM spec by property, through ``build_serving_flow``.

Every spec in ``testkit.SPECS`` is served end to end (scheduler, prefill,
the decode tick loop, the exactly-once sink) and held to three properties:

6. every request is answered exactly once, with its whole budget (the
   kernel path against its ``kernels.ref`` twin is ``test_lm_step.py``'s);
7. greedy tokens are the same at one decode slot and at four;
8. a checkpoint taken while slots are decoding and a request waits for
   a slot, then a kill and a restore, yields the tokens of an
   uninterrupted run.

Five requests meet four slots.  They arrive one at a time, each once the
one before holds a decode slot (in 8, the last once it waits for one), so
prompts join slots that are decoding, and every prefill is of one prompt
(one program a spec, shared by every slot count).  The prompts run past
the specs' 8-position windows or cross them while they decode.
"""
import functools
import threading
import time

import pytest
from testkit import SPECS, spec_id, wait_until

from repro import Session
from repro.checkpoint.checkpointer import _read_floe_state
from repro.serving import build_serving_flow, kv, make_request

pytestmark = pytest.mark.parametrize("spec", SPECS, ids=spec_id)

PROMPTS = [[1 + (3 * i + j) % 60 for j in range(n)]
           for i, n in enumerate((12, 3, 9, 17, 7))]
#: one request more than the four slots; the shortest budget is the one
#: the restore test leaves waiting
BUDGETS = [5, 3, 6, 9, 4]
N_SLOTS = 4


def _inject(session):
    """Each request once the one before it holds a decode slot."""
    decode = session.coordinator.flakes["decode"]._proto
    for i, (p, b) in enumerate(zip(PROMPTS, BUDGETS)):
        session.inject("sched", make_request(i, p, max_new=b))
        assert wait_until(lambda: decode.n_spliced > i, timeout=60)


def _flow(spec, n_slots):
    return build_serving_flow(spec=spec, n_slots=n_slots, max_prompt=24,
                              seed=2)


def _answers(results):
    return [r for r in results if isinstance(r, dict) and "rid" in r]


@functools.lru_cache(maxsize=None)
def _serve(spec, n_slots):
    """Serve the requests once: the answers, and whether the Prometheus
    scrape counts expert fetches."""
    with _flow(spec, n_slots).session() as s:
        _inject(s)
        out = _answers(s.results(timeout=90))
        fetches = "floe_moe_expert_fetches_total" in s.telemetry.prometheus()
    return tuple(sorted(out, key=lambda r: r["rid"])), fetches


def _tokens(answers):
    return {r["rid"]: r["tokens"] for r in answers}


def test_every_request_answered_once_with_its_budget(spec):
    answers, fetches = _serve(spec, N_SLOTS)
    assert [r["rid"] for r in answers] == list(range(len(PROMPTS)))
    assert [r["n_new"] for r in answers] == BUDGETS
    assert all(len(r["tokens"]) == r["n_new"] for r in answers)
    assert all(0 <= t < spec.vocab for r in answers for t in r["tokens"])
    assert fetches == bool(spec.n_experts)


def test_tokens_do_not_depend_on_the_slot_count(spec):
    one, _ = _serve(spec, 1)
    four, _ = _serve(spec, N_SLOTS)
    assert _tokens(one) == _tokens(four)


def test_restore_mid_decode_yields_the_uninterrupted_tokens(
        spec, tmp_path, monkeypatch):
    """The four longest budgets fill the slots and the fifth request waits
    in the scheduler's queue, so the cut holds every slot decoding, the
    waiting queue and the empty free-slot pool.  The decode steps are
    paced up to the cut, so that the slots stay busy well past it."""
    real_step = kv.decode_step
    cut_taken = threading.Event()

    def paced_step(*args, **kwargs):
        if not cut_taken.is_set():
            time.sleep(0.1)
        return real_step(*args, **kwargs)

    monkeypatch.setattr(kv, "decode_step", paced_step)
    flow = _flow(spec, N_SLOTS)
    path = str(tmp_path / "serving.ckpt")
    *live, waiter = sorted(range(len(PROMPTS)), key=lambda r: -BUDGETS[r])
    s = flow.session().open()
    try:
        decode = s.coordinator.flakes["decode"]._proto
        for n, rid in enumerate(live):
            s.inject("sched", make_request(rid, PROMPTS[rid],
                                           max_new=BUDGETS[rid]))
            assert wait_until(lambda: decode.n_spliced > n, timeout=60)
        s.inject("sched", make_request(waiter, PROMPTS[waiter],
                                       max_new=BUDGETS[waiter]))
        sched = s.coordinator.flakes["sched"]
        assert wait_until(lambda: sched.state["waiting"], timeout=60)
        s.checkpoint(path)
    finally:
        cut_taken.set()
        before = _answers([m.payload for m in s.coordinator.outputs])
        s.close()                                  # the kill
    cut = _read_floe_state(path)
    assert [r["rid"] for r in cut["sched"]["state"]["waiting"]] == [waiter]
    assert cut["sched"]["state"]["free"] == []
    assert cut["decode"]["pellet"]["live"].all()
    with Session.restore(path, flow) as restored:
        after = _answers(restored.results(timeout=90))
    want = _tokens(_serve(spec, N_SLOTS)[0])
    assert waiter not in {r["rid"] for r in before}
    got = {}
    for r in before + after:
        assert r["tokens"] == want[r["rid"]], r["rid"]
        got[r["rid"]] = r["tokens"]
    assert got == want
