"""Program tracing: each request's stamps from the front door's socket to
the SSE write, the serve loop's phases partitioning the time between step
records, spans per thread in the Chrome trace, the tracer's cost when only
the profiler reads it, the step programs' names and the run-end exporter's
compiled reduction."""

import json
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_arch
from repro.core.sparsity import bit_sparsity_sign_magnitude
from repro.models import api
from repro.models.layers import quantize_dense_params
from repro.serving import (FrontDoor, FrontDoorClient, Replica, Request,
                           ServeConfig, ServingEngine, Telemetry)
from repro.serving.telemetry import (NULL_SPAN, OTHER_PHASE, STEP_SCHEMA,
                                     PhaseClock)

jax.config.update("jax_default_matmul_precision", "float32")

LOOP_PHASES = {"loop.inbox", "loop.sweep", "loop.plan", "prefill.stage",
               "decode.prepare", "decode.stage", "loop.notify"}
FRONT_DOOR_SPANS = {"http.parse", "route", "sse.write"}


def _dense_cfg(**kw):
    return get_arch("qwen2-1.5b").reduced().replace(
        num_layers=2, d_model=64, d_ff=128, vocab_size=128, head_dim=16, **kw)


@pytest.fixture(scope="module")
def dense():
    cfg = _dense_cfg()
    return cfg, api.init(jax.random.PRNGKey(0), cfg)


def _engine(dense, telemetry=None, max_new=6, backend="paged"):
    cfg, params = dense
    return ServingEngine(cfg, params, ServeConfig(
        max_new_tokens=max_new, temperature=0.0, cache_backend=backend,
        block_size=4, telemetry=telemetry))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(2, 128, n).astype(np.int32)


def _step_records(stream):
    return [r for r in stream if r["kind"] in ("prefill", "decode", "verify")]


@pytest.fixture(scope="module")
def served(dense, tmp_path_factory):
    """Four streamed requests, two at a time, through a real front door
    over one replica with metrics and a Chrome trace."""
    d = tmp_path_factory.mktemp("door")
    tel = Telemetry(metrics_path=str(d / "metrics.jsonl"),
                    trace_path=str(d / "trace.json"))
    replica = Replica(_engine(dense, telemetry=tel), name="r0", n_slots=2,
                      cache_T=64)
    door = FrontDoor([replica]).start()
    client = FrontDoorClient("127.0.0.1", door.port)
    out = {}

    def send(i):
        out[i] = client.generate(_prompt(9 + i, seed=i), max_new_tokens=6,
                                 stream=True)

    try:
        for pair in ((0, 1), (2, 3)):
            threads = [threading.Thread(target=send, args=(i,))
                       for i in pair]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive()
    finally:
        door.stop()
    tel.close()
    assert all(len(o["tokens"]) == 6 for o in out.values())
    with open(d / "trace.json") as f:
        events = json.load(f)["traceEvents"]
    return replica.loop, events


class TestRequestStamps:
    def test_stamps_are_ordered_from_socket_to_write(self, served):
        loop, _ = served
        assert len(loop.requests) == 4
        for req in loop.requests:
            first_token, first_write = (req.wall_token_times[0],
                                        req.wall_written_times[0])
            assert (req.wall_received_at <= req.wall_enqueued_at
                    <= req.wall_drained_at <= req.wall_submitted_at
                    <= req.wall_admitted_at <= first_token <= first_write)
            assert len(req.wall_written_times) == len(req.tokens) == 6
            assert all(t <= w for t, w in zip(req.wall_token_times,
                                              req.wall_written_times))

    def test_record_parts_add_up_to_the_server_side_wait(self, served):
        loop, _ = served
        by_id = {r.request_id: r for r in loop.requests}
        recs = [r for r in loop.stream if r["kind"] == "request"]
        assert len(recs) == 4
        for rec in recs:
            assert STEP_SCHEMA["request"] <= set(rec)
            req = by_id[rec["request_id"]]
            parts = (rec["ingress_s"] + rec["inbox_s"] + rec["hold_s"]
                     + rec["queue_wait_s"])
            assert parts == pytest.approx(
                req.wall_admitted_at - req.wall_received_at, abs=1e-9)
            assert min(rec["ingress_s"], rec["inbox_s"], rec["hold_s"]) >= 0
            assert len(rec["egress_s"]) == rec["n_tokens"]
            assert min(rec["egress_s"]) >= 0

    def test_batch_serve_has_no_front_door_stamps(self, dense):
        loop = _engine(dense).make_loop(
            [Request(prompt=_prompt(8, seed=i), max_new_tokens=4)
             for i in range(2)], n_slots=2, cache_T=32)
        loop.run()
        for rec in (r for r in loop.stream if r["kind"] == "request"):
            assert rec["ingress_s"] is None and rec["inbox_s"] is None
            assert rec["hold_s"] is None and rec["egress_s"] == []
            assert rec["queue_wait_s"] >= 0


class TestSpansPerThread:
    def test_front_door_and_loop_spans_nest_per_thread(self, served):
        _, events = served
        spans = [e for e in events if e["ph"] == "X"]
        names = {e["name"] for e in spans}
        assert FRONT_DOOR_SPANS | LOOP_PHASES <= names
        assert {"prefill", "install", "decode", "commit"} <= names
        tid_of = {}
        for e in spans:
            tid_of.setdefault(e["name"], set()).add(e["tid"])
        door_tids = set().union(*(tid_of[n] for n in FRONT_DOOR_SPANS))
        loop_tids = set().union(*(tid_of[n] for n in LOOP_PHASES))
        assert len(door_tids) == 1 and len(loop_tids) == 1
        assert door_tids != loop_tids
        thread_names = {e["tid"]: e["args"]["name"] for e in events
                        if e["ph"] == "M" and e["name"] == "thread_name"}
        assert thread_names[next(iter(door_tids))] == "frontdoor-http"
        assert thread_names[next(iter(loop_tids))] == "replica-r0"
        by_tid = {}
        for e in spans:
            by_tid.setdefault(e["tid"], []).append(e)
        for tid_spans in by_tid.values():
            tid_spans.sort(key=lambda e: (e["ts"], -e["dur"]))
            for i, a in enumerate(tid_spans):
                a0, a1 = a["ts"], a["ts"] + a["dur"]
                for b in tid_spans[i + 1:]:
                    b0, b1 = b["ts"], b["ts"] + b["dur"]
                    if b0 >= a1 - 1e-6:
                        break
                    assert b1 <= a1 + 1e-6, (a, b)   # overlap: must nest

    def test_step_record_and_span_share_one_clock(self, served):
        loop, events = served
        decodes = [e for e in events if e["ph"] == "X"
                   and e["name"] == "decode"]
        recs = [r for r in loop.stream if r["kind"] == "decode"]
        assert len(decodes) == len(recs)
        for ev, rec in zip(decodes, recs):
            # the dispatch span lies inside its record's interval
            start_us = 1e6 * (rec["ts_s"] - rec["wall_s"])
            assert start_us - 1e-3 <= ev["ts"]
            assert ev["ts"] + ev["dur"] <= 1e6 * rec["ts_s"] + 1e-3
            assert ev["dur"] == pytest.approx(
                1e6 * rec["phases"]["dispatch_s"], rel=1e-9, abs=1e-6)


class TestLoopPhases:
    @staticmethod
    def _check_partition(stream):
        steps = _step_records(stream)
        assert len(steps) >= 3
        for prev, rec in zip(steps, steps[1:]):
            since = rec["ts_s"] - prev["ts_s"]
            covered = sum(rec["phases"].values())
            assert abs(covered - since) <= max(0.02 * since, 2e-4), rec
        return steps

    def test_decode_phases_cover_time_since_previous_record(self, dense):
        reqs = [Request(prompt=_prompt(6 + i, seed=i), max_new_tokens=5,
                        arrival_time=float(i)) for i in range(4)]
        loop = _engine(dense).make_loop(reqs, n_slots=2, cache_T=32)
        loop.run()
        steps = self._check_partition(loop.stream)
        decodes = [r for r in steps if r["kind"] == "decode"]
        assert decodes
        for rec in decodes:
            assert {"prepare_s", "dispatch_s", "commit_s", "stage_s",
                    OTHER_PHASE} <= set(rec["phases"])
            assert rec["wall_s"] >= rec["phases"]["dispatch_s"] >= 0
        assert any({"notify_s", "inbox_s", "sweep_s", "plan_s"}
                   <= set(r["phases"]) for r in decodes)
        for rec in (r for r in steps if r["kind"] == "prefill"):
            assert {"dispatch_s", "install_s", "stage_s"} <= set(
                rec["phases"])

    def test_direct_drive_stamps_like_run_forever(self, dense):
        """The benchmark's warm-up drives ``_drain_inbox``, ``sweep`` and
        ``_step`` itself, outside ``run_forever``."""
        loop = _engine(dense).make_loop([], n_slots=2, cache_T=32)
        for i in range(3):
            loop.submit(Request(prompt=_prompt(8, seed=i),
                                max_new_tokens=3))
        while True:
            loop._drain_inbox()
            if not (loop.arrivals or len(loop.rq) or loop.active):
                break
            loop.sweep()
            loop._step()
        steps = self._check_partition(loop.stream)
        first = steps[0]["phases"]
        assert {"inbox_s", "sweep_s", "plan_s", "stage_s"} <= set(first)
        loop._emit_request_records()
        for rec in (r for r in loop.stream if r["kind"] == "request"):
            assert rec["ingress_s"] is None
            assert rec["inbox_s"] >= 0 and rec["hold_s"] >= 0

    def test_speculative_steps_partition_too(self, dense):
        cfg, params = dense
        eng = ServingEngine(cfg, params, ServeConfig(
            max_new_tokens=6, temperature=0.0, cache_backend="paged",
            block_size=4, draft="prompt_lookup", num_draft_tokens=3))
        phrase = _prompt(4, seed=7)
        reqs = [Request(prompt=np.concatenate([phrase, phrase,
                                               _prompt(2, seed=i)]),
                        max_new_tokens=6) for i in range(2)]
        loop = eng.make_loop(reqs, n_slots=2, cache_T=40)
        loop.run()
        steps = self._check_partition(loop.stream)
        verify = [r for r in steps if r["kind"] == "verify"]
        assert verify
        for rec in verify:
            assert {"draft_s", "prepare_s", "stage_s", "dispatch_s",
                    "commit_s", "rollback_s"} <= set(rec["phases"])


class TestTracerCost:
    def test_phase_clock_stamps_without_tracing(self):
        tel = Telemetry()
        clock = PhaseClock(tel)
        assert tel.span("loop.plan") is NULL_SPAN
        with clock("loop.plan", "plan_s") as ph:
            pass
        with clock("loop.plan", "plan_s"):
            pass
        assert ph.t1 >= ph.t0 > 0
        end = ph.t1 + 0.5
        phases = clock.take(end, "dispatch_s")
        assert phases["dispatch_s"] == 0.0 and phases["plan_s"] >= 0
        assert phases[OTHER_PHASE] >= 0.5
        assert clock.take(end + 1.0) == {OTHER_PHASE: 1.0}

    def test_profiler_only_tracer_keeps_no_events(self, tmp_path):
        tel = Telemetry(annotate_device=True)
        assert tel.tracer is not None and tel.tracer.events is None
        with tel.span("decode", n_slots=2):
            pass
        with PhaseClock(tel)("loop.sweep", "sweep_s"):
            pass
        tel.instant("x")
        tel.counter("c", v=1)
        tel.record_span("route", 0.0, 1.0)
        tel.flush()
        assert tel.tracer.events is None

    def test_written_trace_keeps_every_thread(self, tmp_path):
        path = tmp_path / "t.json"
        tel = Telemetry(trace_path=str(path))

        def work():
            with tel.span("sse.write"):
                pass

        t = threading.Thread(target=work, name="writer")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with tel.span("decode"):
            pass
        tel.close()
        events = json.loads(path.read_text())["traceEvents"]
        spans = {e["name"]: e["tid"] for e in events if e["ph"] == "X"}
        assert spans["sse.write"] != spans["decode"]
        names = {e["tid"]: e["args"]["name"] for e in events
                 if e["name"] == "thread_name"}
        assert names[spans["sse.write"]] == "writer"


class TestProgramNames:
    def test_step_programs_carry_stable_names(self, dense):
        cfg, params = dense
        eng = ServingEngine(cfg, params, ServeConfig(
            max_new_tokens=4, temperature=0.0, cache_backend="paged",
            block_size=4, draft="prompt_lookup", num_draft_tokens=2))
        loop = eng.make_loop([Request(prompt=_prompt(8, seed=1),
                                      max_new_tokens=4)],
                             n_slots=2, cache_T=32)
        cache = loop.cm.cache
        n = loop.n_slots
        step = {"tokens": jnp.zeros((n, 1), jnp.int32),
                "cache_len": loop.cm.cache_len_vector(),
                "block_tables": loop.cm.block_tables_device()}
        text = loop._decode_fn.lower(
            cache, step, jnp.zeros((n, 2), jnp.uint32),
            jnp.zeros((n,), jnp.uint32)).as_text()
        assert "@jit_decode_step" in text
        text = loop._verify_fn.lower(
            cache, dict(step, tokens=jnp.zeros((n, 3), jnp.int32))).as_text()
        assert "@jit_verify_step" in text
        ex = eng.executor
        ex.prefill({"tokens": np.ones((1, 8), np.int32)}, 32,
                   prompt_lens=np.asarray([8], np.int32))
        prefill = ex._jits[("prefill_ragged", False)]
        text = prefill.lower(ex.params, {"tokens": jnp.ones((1, 8),
                                                             jnp.int32)},
                             32, jnp.asarray([8], jnp.int32)).as_text()
        assert "@jit_prefill_step" in text


class TestDeploymentEstimate:
    def test_fused_reduction_matches_the_eager_one(self, dense):
        cfg, params = dense
        qcfg = cfg.replace(matmul_mode="bp_exact")
        qparams = quantize_dense_params(params)
        engine = ServingEngine(qcfg, qparams, ServeConfig(max_new_tokens=2))
        est = engine.deployment_estimate(n_mc=500)
        L = qcfg.num_layers
        want = {}
        for leaf in jax.tree.leaves(engine.params):
            if not (hasattr(leaf, "dtype") and leaf.dtype == jnp.int8):
                continue
            if leaf.ndim >= 2 and leaf.shape[0] == L:
                for l in range(L):
                    want.setdefault(l, []).append(
                        float(bit_sparsity_sign_magnitude(leaf[l])))
            else:
                want.setdefault(-1, []).append(
                    float(bit_sparsity_sign_magnitude(leaf)))
        got = {e["layer"]: e["bit_sparsity"] for e in est["per_layer"]}
        assert set(got) == set(want)
        for layer, values in want.items():
            # float32 sums in another fusion: equal to the dtype's rounding
            assert got[layer] == pytest.approx(float(np.mean(values)),
                                               rel=1e-6)
