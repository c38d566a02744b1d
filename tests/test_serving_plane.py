"""Serving plane: multi-column carriers, exactly-once sinks, LM dataflow.

Covers PR 8's tentpole and satellites:

* multi-column ``ArrayBatch`` (dict-of-arrays) semantics
* ``__floe_state__`` carry-over across in-place task updates
* ``Flow.sink(..., exactly_once=True)`` dedup end-to-end
* the serving dataflow itself — continuous-batching census, counters and
  profiler spans, and zero-loss live weight hot-swap with version tags
  (every spec's answers, slot-count invariance and checkpoint→kill→restore
  of in-flight generations are ``test_lm_flow.py``'s).
"""
import contextlib
import pickle
import time

import numpy as np
import pytest
from testkit import wait_until

from repro import Flow, FnPellet, PushPellet
from repro.core.arraybatch import ArrayBatch
from repro.serving import (LMSpec, Scheduler, build_serving_flow, kv,
                           make_request, swapped_flow)
from repro.telemetry import parse_prometheus

#: one tiny geometry shared by every dataflow test — jit caches per
#: (spec, shapes), so reuse keeps the suite to a handful of compiles
SPEC = LMSpec(vocab=16, n_heads=2, n_kv_heads=1, head_dim=4, n_layers=1,
              max_len=16)


def _responses(results):
    return sorted((r for r in results if isinstance(r, dict) and "rid" in r),
                  key=lambda r: r["rid"])


# ---------------------------------------------------------------------------
# satellite: multi-column ArrayBatch
# ---------------------------------------------------------------------------

class TestMultiColumnArrayBatch:
    def test_stack_dict_payloads_columnwise(self):
        rows = [{"tok": np.int32(i), "slot": np.int32(9 - i),
                 "vec": np.full(3, float(i))} for i in range(4)]
        ab = ArrayBatch.try_stack(rows)
        assert ab is not None and len(ab) == 4
        assert set(ab.columns) == {"tok", "slot", "vec"}
        assert ab.columns["vec"].shape == (4, 3)
        np.testing.assert_array_equal(ab.columns["tok"], [0, 1, 2, 3])

    def test_row_access_and_messages(self):
        ab = ArrayBatch({"a": np.arange(3), "b": np.arange(3) * 10.0},
                        seqs=[7, 8, 9])
        row = ab._row(1)
        assert row == {"a": 1, "b": 10.0}
        msgs = ab.to_messages()
        assert [m.payload["b"] for m in msgs] == [0.0, 10.0, 20.0]
        assert msgs[2].meta["parent_seq"] == 9

    def test_take_slices_every_column(self):
        ab = ArrayBatch({"x": np.arange(5), "y": np.arange(5) * 2},
                        keys=list("abcde"))
        sub = ab.take([4, 0])
        assert len(sub) == 2
        np.testing.assert_array_equal(sub.columns["y"], [8, 0])
        assert sub.keys == ["e", "a"]

    def test_ragged_or_heterogeneous_dicts_decline(self):
        # different key sets -> decline
        assert ArrayBatch.try_stack([{"a": 1}, {"b": 2}]) is None
        # ragged column shapes -> decline
        assert ArrayBatch.try_stack(
            [{"a": np.zeros(2)}, {"a": np.zeros(3)}]) is None
        # object column -> decline
        assert ArrayBatch.try_stack([{"a": object()}, {"a": object()}]) is None

    def test_constructor_rejects_ragged_columns(self):
        with pytest.raises(ValueError):
            ArrayBatch({"a": np.zeros(2), "b": np.zeros(3)})
        with pytest.raises(ValueError):
            ArrayBatch({})

    def test_pickle_roundtrip_materializes_host(self):
        ab = ArrayBatch({"a": np.arange(4), "b": np.ones((4, 2))})
        ab2 = pickle.loads(pickle.dumps(ab))
        assert len(ab2) == 4
        np.testing.assert_array_equal(ab2.columns["a"], np.arange(4))

    def test_single_array_unchanged(self):
        ab = ArrayBatch.try_stack([np.ones(2), np.ones(2)])
        assert ab.columns is None and ab.array.shape == (2, 2)
        assert ab._row(0).shape == (2,)


# ---------------------------------------------------------------------------
# satellite groundwork: __floe_state__ survives an in-place task update
# ---------------------------------------------------------------------------

class _Accum(PushPellet):
    sequential = True
    __floe_state__ = ("total",)

    def __init__(self, gain):
        self.gain = gain
        self.total = 0

    def compute(self, payload):
        self.total += payload
        return self.total * self.gain


class TestSwapCarriesInstanceState:
    def test_swap_pellet_carries_floe_state(self):
        flow = Flow("carry")
        acc = flow.pellet("acc", lambda: _Accum(1))
        with flow.session() as s:
            s.inject(acc, 5)
            assert s.results(timeout=10) == [5]
            s.update(acc, lambda: _Accum(10))
            s.inject(acc, 1)
            # total=5 carried across the swap: (5+1)*10, not 1*10
            assert s.results(timeout=10) == [60]


# ---------------------------------------------------------------------------
# satellite: exactly-once sink
# ---------------------------------------------------------------------------

class TestExactlyOnceSink:
    def test_dedups_by_rid(self):
        flow = Flow("eos")
        src = flow.pellet("src", lambda: FnPellet(lambda x: x))
        delivered = []
        sink = flow.sink("sink", delivered.append, exactly_once=True)
        src >> sink
        with flow.session() as s:
            for rid in (1, 2, 1, 3, 2, 1):
                s.inject(src, {"rid": rid, "body": rid * 10})
            out = s.results(timeout=10)
        assert sorted(r["rid"] for r in out) == [1, 2, 3]
        assert sorted(r["rid"] for r in delivered) == [1, 2, 3]

    def test_custom_key_and_state_counts(self):
        flow = Flow("eos2")
        src = flow.pellet("src", lambda: FnPellet(lambda x: x))
        sink = flow.sink("sink", exactly_once=True, key=lambda p: p % 4)
        src >> sink
        with flow.session() as s:
            s.inject_many(src, list(range(8)))
            out = s.results(timeout=10)
            st = s.coordinator.flakes["sink"].state
        assert sorted(p % 4 for p in out) == [0, 1, 2, 3]
        assert st["delivered"] == 4 and st["duplicates"] == 4

    def test_plain_sink_passthrough(self):
        flow = Flow("plain")
        src = flow.pellet("src", lambda: FnPellet(lambda x: x))
        seen = []
        sink = flow.sink("sink", seen.append)
        src >> sink
        with flow.session() as s:
            s.inject_many(src, [1, 1, 2])
            assert sorted(s.results(timeout=10)) == [1, 1, 2]
        assert sorted(seen) == [1, 1, 2]

    def test_key_requires_exactly_once(self):
        from repro import CompositionError
        with pytest.raises(CompositionError):
            Flow("bad").sink("s", key=lambda p: p)


# ---------------------------------------------------------------------------
# tentpole: the serving dataflow
# ---------------------------------------------------------------------------

class TestServingPlane:
    def test_census_continuous_batching(self):
        """All requests complete through a 2-slot decode tier; concurrent
        slots share decode steps (the continuous-batching census)."""
        flow = build_serving_flow(spec=SPEC, n_slots=2, default_budget=4,
                                  seed=0)
        with flow.session() as s:
            s.inject_many("sched", [make_request(i, [1 + i, 2, 3], max_new=4)
                                    for i in range(6)])
            resp = _responses(s.results(timeout=90))
            sched_state = s.coordinator.flakes["sched"].state
            decode = s.coordinator.flakes["decode"]._proto
            assert s.telemetry.array_hits.labels(
                stage="prefill").value >= 6
        assert [r["rid"] for r in resp] == [0, 1, 2, 3, 4, 5]
        assert all(r["n_new"] == 4 for r in resp)
        assert all(r["version"] == 0 for r in resp)
        assert all(r["t_sub"] <= r["t_first"] <= r["t_done"] for r in resp)
        # slot lifecycle closed the loop: every slot freed and re-usable
        assert sched_state["admitted"] == 6 and sched_state["freed"] == 6
        assert sorted(sched_state["free"]) == [0, 1]
        assert decode.n_spliced == 6 and not decode.live.any()
        # census: 6 requests x 3 decode steps each would be 18 solo steps;
        # sharing the slot batch must cut that down
        assert decode.n_steps < 18

    def test_counters_census(self):
        """The serving counters agree with what the flow did: a tick-wait
        sample per decode step, one token per live slot a step, every
        real prompt token and every padded position prefill computed —
        read from the Prometheus text an operator scrapes."""
        prompts = [[1 + i] * (1 + i % 5) for i in range(7)]
        budgets = [2 + i % 4 for i in range(7)]
        flow = build_serving_flow(spec=SPEC, n_slots=2, default_budget=4,
                                  seed=0)
        with flow.session() as s:
            s.inject_many("sched", [make_request(i, p, max_new=b)
                                    for i, (p, b) in
                                    enumerate(zip(prompts, budgets))])
            resp = _responses(s.results(timeout=90))
            decode = s.coordinator.flakes["decode"]._proto
            max_prompt = s.coordinator.flakes["sched"]._proto.max_prompt
            text = s.telemetry.prometheus()
        series = parse_prometheus(text)

        def value(name, stage):
            hits = [v for labels, v in series[name]
                    if labels == {"stage": stage}]
            assert len(hits) == 1, (name, series.get(name))
            return hits[0]

        assert [r["n_new"] for r in resp] == budgets
        assert decode.n_steps > 0
        assert value("floe_decode_steps_total", "decode") == decode.n_steps
        assert value("floe_decode_tick_wait_seconds_count", "decode") == \
            decode.n_steps
        assert value("floe_decode_tick_wait_seconds_sum", "decode") > 0.0
        assert value("floe_decode_tokens_total", "decode") == \
            sum(r["n_new"] - 1 for r in resp)
        assert value("floe_prefill_tokens_total", "prefill") == \
            sum(min(len(p), max_prompt) for p in prompts)
        # every request is prefilled once, in a batch padded to max_prompt
        assert value("floe_prefill_positions_total", "prefill") == \
            len(prompts) * max_prompt
        # one tile holds the whole tiny cache: every slot, layer and K/V
        # reads it, live or dead
        tiles = decode.n_steps * 2 * SPEC.n_layers * decode.n_slots
        assert value("floe_decode_kv_tiles_total", "decode") == tiles
        assert value("floe_decode_kv_tiles_read_total", "decode") == tiles

    def test_profiler_spans_nest_in_dispatch(self, tmp_path):
        """Under the profiler, every decode step leaves one launch, one
        sync and one bookkeeping span tagged ``stage="decode"``, each
        inside a ``floe.dispatch`` of the decode stage on its thread."""
        import jax
        from jax.profiler import ProfileData
        flow = build_serving_flow(spec=SPEC, n_slots=2, default_budget=4,
                                  seed=0)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with flow.session() as s:
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                s.inject_many("sched", [make_request(i, [2 + i, 3],
                                                     max_new=3 + i)
                                        for i in range(4)])
                resp = _responses(s.results(timeout=90))
            finally:
                jax.profiler.stop_trace()
            steps = s.coordinator.flakes["decode"]._proto.n_steps
        assert len(resp) == 4 and steps > 0
        path = next(tmp_path.rglob("*.xplane.pb"))
        spans = []                  # (name, stage, start, end, line)
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for li, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("floe."):
                        stage = dict(e.stats).get("stage")
                        spans.append((e.name, stage, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      (plane.name, li)))
        dispatches = [sp for sp in spans
                      if sp[0] == "floe.dispatch" and sp[1] == "decode"]
        for name in ("floe.decode.launch", "floe.decode.sync",
                     "floe.decode.bookkeep"):
            mine = [sp for sp in spans if sp[0] == name]
            assert len(mine) == steps, (name, len(mine), steps)
            assert all(sp[1] == "decode" for sp in mine)
            for _, _, start, end, where in mine:
                assert any(d[4] == where and d[2] <= start and end <= d[3]
                           for d in dispatches), (name, start)
        names = {sp[0] for sp in spans}
        assert {"floe.inject", "floe.route", "floe.decode.splice",
                "floe.prefill.launch", "floe.prefill.sync"} <= names

    def test_telemetry_off_builds_no_span_and_counts_nothing(
            self, monkeypatch):
        import repro.core.engine as engine_mod
        import repro.serving.dataflow as dataflow_mod
        built = []

        def counting_span(name, **meta):
            built.append(name)
            return contextlib.nullcontext()

        monkeypatch.setattr(engine_mod, "span", counting_span)
        monkeypatch.setattr(dataflow_mod, "span", counting_span)
        seen = {}
        for on in (True, False):
            built.clear()
            flow = build_serving_flow(spec=SPEC, n_slots=2,
                                      default_budget=3, seed=0)
            with flow.session(telemetry=on) as s:
                s.inject_many("sched", [make_request(i, [1 + i, 2],
                                                     max_new=3)
                                        for i in range(3)])
                assert len(_responses(s.results(timeout=90))) == 3
                decode = s.coordinator.flakes["decode"]._proto
                families = set(s.telemetry.metrics())
            seen[on] = (list(built), decode._stage, decode._tick_t,
                        families)
        # the stub is on the path: with telemetry on it builds spans
        assert {"floe.dispatch", "floe.decode.launch"} <= set(seen[True][0])
        assert {"floe_decode_steps_total", "floe_decode_kv_tiles_read_total",
                "floe_decode_kv_tiles_total"} <= seen[True][3]
        spans, stage, tick_t, families = seen[False]
        assert spans == [] and stage is None and tick_t is None
        assert not {f for f in families if f.startswith(
            ("floe_decode_", "floe_prefill_"))}

    @pytest.mark.parametrize("lengths,live,reads", [
        # kernel lengths 1024, 1025, 2 -> 1 + 2 + 1 tiles of 1024, then
        # 1025, 1026, 2 -> 2 + 2 + 1; x 2 layers x K/V
        ([1023, 1024, 1], [True, True, False], [16, 20]),
        # every slot dead but one at length 1: one tile each
        ([1, 1, 1], [True, False, False], [12, 12]),
    ])
    def test_kv_tile_counters(self, lengths, live, reads):
        """The decode step counts the KV tiles the flash-decode kernel
        fetches (``kv_tiles_read`` of the lengths it gets) and the tiles
        the caches hold."""
        from types import SimpleNamespace
        from repro.kernels.decode_attention import kv_block_k, kv_tiles_read
        from repro.serving.dataflow import DecodePellet
        from repro.telemetry import MetricsRegistry
        spec = LMSpec(vocab=16, n_heads=2, n_kv_heads=1, head_dim=4,
                      n_layers=2, max_len=2048)
        assert kv_block_k(spec.max_len, spec.n_kv_heads,
                          spec.head_dim) == 1024
        pellet = DecodePellet(kv.init_params(spec, 0), spec, n_slots=3)
        pellet.bind_telemetry(SimpleNamespace(registry=MetricsRegistry()),
                              "decode")
        for s, (n, on) in enumerate(zip(lengths, live)):
            if on:
                pellet._admit_row({"slot": s, "rid": s, "tok0": 1,
                                   "length": n, "budget": 8, "t_sub": 0.0,
                                   "t_first": 0.0}, [], spliced=True)
        for want in reads:
            before = (pellet._kv_read.value, pellet._kv_tiles.value)
            assert want == 2 * spec.n_layers * kv_tiles_read(
                pellet.lengths + 1, 1024)
            pellet._step([])
            assert pellet._kv_read.value - before[0] == want
            assert pellet._kv_tiles.value - before[1] == 2 * 2 * 3 * 2

    @pytest.mark.parametrize("width", [16, 64])
    def test_prefill_attn_block_counters(self, width):
        """One prefill of the windowed expert spec advances the attention
        block counters by exactly ``band_blocks``' counts, summed over its
        windowed and full layers, batch rows and heads; the ref path
        leaves them alone."""
        from types import SimpleNamespace
        from testkit import SPECS
        from repro.kernels.flash_attention import band_blocks, block_sizes
        from repro.serving.dataflow import PrefillPellet
        from repro.telemetry import MetricsRegistry
        spec = next(sp for sp in SPECS
                    if sp.n_experts and len(set(sp.windows)) > 1)
        B = 2
        want = [0, 0]
        for l in range(spec.n_layers):
            w = spec.window(l)
            counts = band_blocks(width, width,
                                 *block_sizes(width, width), True, w)
            want = [a + B * spec.n_heads * c for a, c in zip(want, counts)]
        cols = {"tokens": np.ones((B, width), np.int32),
                "length": np.array([width, 3], np.int32),
                "rid": np.arange(B, dtype=np.int32),
                "slot": np.arange(B, dtype=np.int32),
                "budget": np.full(B, 4, np.int32), "t_sub": np.zeros(B)}
        telemetry = SimpleNamespace(registry=MetricsRegistry())
        params = kv.init_params(spec, 0)
        kernel = PrefillPellet(params, spec)
        kernel.bind_telemetry(telemetry, "prefill")
        kernel.compute_array(cols)
        assert [kernel._attn_visited.value,
                kernel._attn_blocks.value] == want
        ref = PrefillPellet(params, spec, ref_path=True)
        ref.bind_telemetry(telemetry, "prefill")
        ref.compute_array(cols)
        assert ref._attn_visited is None and ref._attn_blocks is None
        assert [kernel._attn_visited.value,
                kernel._attn_blocks.value] == want

    def test_paired_requests_share_steps(self):
        flow = build_serving_flow(spec=SPEC, n_slots=2, default_budget=4,
                                  seed=0)
        with flow.session() as s:
            s.inject_many("sched",
                          [make_request(i, [3, 1], max_new=4)
                           for i in range(2)])
            resp = _responses(s.results(timeout=90))
            steps = s.coordinator.flakes["decode"]._proto.n_steps
        assert len(resp) == 2
        # both slots ride the same step batch: ~3 shared steps, never the
        # 6 a sequential tier would need (small slack for admission skew)
        assert steps <= 4

    def test_hot_swap_zero_loss_version_tags(self, monkeypatch):
        """Live weight hot-swap mid-stream: every request answered exactly
        once; completions before the swap tag version 0, after it version
        1; the in-flight generation crosses the swap intact."""
        # pace the decode steps: unpaced, the carried generation's 11 steps
        # take ~15 ms on a CPU and, on a loaded host, finished before the
        # swap landed (the test then saw version 0)
        real_step = kv.decode_step

        def paced_step(*args, **kwargs):
            time.sleep(0.02)
            return real_step(*args, **kwargs)

        monkeypatch.setattr(kv, "decode_step", paced_step)
        flow = build_serving_flow(spec=SPEC, n_slots=2, default_budget=3,
                                  seed=0, version=0)
        with flow.session() as s:
            coord = s.coordinator
            # wave 1 completes under v0
            s.inject_many("sched", [make_request(i, [1 + i, 2], max_new=3)
                                    for i in range(2)])
            assert wait_until(
                lambda: len(_responses(
                    [m.payload for m in coord.outputs])) >= 2, timeout=60)
            # a long-running generation to carry across the swap
            s.inject("sched", make_request(10, [3, 4], max_new=12))
            decode = coord.flakes["decode"]._proto
            assert wait_until(lambda: decode.live.any(), timeout=60)
            summary = s.apply(swapped_flow(flow, seed=1, version=1))
            assert sorted(summary["swapped"]) == ["decode", "prefill"]
            # wave 2 completes under v1
            s.inject_many("sched",
                          [make_request(20 + i, [5, 1 + i], max_new=3)
                           for i in range(2)])
            resp = _responses(s.results(timeout=90))
        versions = {r["rid"]: r["version"] for r in resp}
        assert sorted(versions) == [0, 1, 10, 20, 21], \
            f"requests lost across hot-swap: {sorted(versions)}"
        assert len(resp) == 5          # deduped: exactly one response each
        assert versions[0] == 0 and versions[1] == 0
        assert versions[20] == 1 and versions[21] == 1
        carried = next(r for r in resp if r["rid"] == 10)
        # the mid-flight generation crossed the swap without restarting
        assert carried["n_new"] == 12
        assert carried["version"] == 1

    def test_scheduler_rejects_replayed_admission(self):
        sched = Scheduler(n_slots=2, max_prompt=4, max_len=16)
        state = sched.initial_state()

        class _M:
            def __init__(self, p):
                self.payload = p

            def is_data(self):
                return True

        out = []
        req = make_request(1, [1, 2], max_new=2)
        sched.compute([_M(req), _M(dict(req))], out.append, state)
        assert len(out) == 1 and state["rejected"] == 1
