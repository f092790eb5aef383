"""Chaos suite: seeded fault injection against the serve loop.

The correctness anchor is the same as everywhere else in the serving
stack — TOKEN IDENTITY.  A run under a seeded fault schedule must produce,
for every request that still finishes normally, exactly the tokens of the
fault-free run: retries re-dispatch untouched steps, recoveries rebuild
the executor and replay token-exact, the NaN guard fails only the
poisoned slot, and cancellations/timeouts release every block they held.
"""

import numpy as np
import pytest
import jax

from repro.configs.base import get_arch
from repro.models import api
from repro.serving import (NULL_INJECTOR, FaultInjector, Request,
                           RequestState, SchedulerConfig, ServeConfig,
                           ServingEngine, StepCompileError)

jax.config.update("jax_default_matmul_precision", "float32")

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _dense_cfg():
    return get_arch("qwen2-1.5b").reduced().replace(
        num_layers=2, d_model=64, d_ff=128, vocab_size=128, head_dim=16)


CFG = _dense_cfg()
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = api.init(jax.random.PRNGKey(0), CFG)
    return _PARAMS


def _prompt(S, seed):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (S,), 2,
                                         CFG.vocab_size), np.int32)


def _requests():
    """A small heterogeneous stream; index i is comparable across runs."""
    spec = [(6, 1, 8, 0.0), (6, 1, 5, 0.0),   # shared prompt: prefix hits
            (4, 2, 7, 1.0), (5, 3, 6, 2.0), (7, 4, 8, 4.0)]
    return [Request(prompt=_prompt(S, seed), max_new_tokens=m,
                    arrival_time=t) for S, seed, m, t in spec]


def _serve(backend="slab", draft="none", faults=None, num_blocks=None,
           lead_window=2, **cfg_over):
    cfg_kw = dict(max_new_tokens=8, temperature=0.0, cache_backend=backend,
                  block_size=4, draft=draft, num_draft_tokens=3,
                  faults=faults)
    cfg_kw.update(cfg_over)
    engine = ServingEngine(CFG, _params(), ServeConfig(**cfg_kw))
    reqs = _requests()
    loop = engine.make_loop(reqs, n_slots=2, num_blocks=num_blocks,
                            sched_cfg=SchedulerConfig(
                                lead_window=lead_window))
    report = loop.run()
    return report, loop, reqs


_BASELINES = {}


def _baseline(backend, draft):
    """Fault-free reference tokens, one serve per (backend, draft)."""
    key = (backend, draft)
    if key not in _BASELINES:
        report, _, _ = _serve(backend, draft)
        _BASELINES[key] = [list(r.tokens) for r in report.results]
    return _BASELINES[key]


def _tokens(report):
    return [list(r.tokens) for r in report.results]


def _assert_pool_drained(loop):
    """After the queue drains, the paged pool must be leak-free: no live
    blocks, free+cached partition covering everything but the trash
    block, zero refcounts."""
    if not loop.paged:
        return
    pool = loop.cm.pool
    assert pool.n_live == 0
    assert pool.n_free == pool.num_blocks - 1
    assert int(pool.refcount.sum()) == 0


def _injected_fault_records(loop):
    return [r for r in loop.stream
            if r["kind"] == "fault" and r.get("injected")]


# ---------------------------------------------------------------------------
# NULL_INJECTOR is a strict no-op
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,draft",
                         [("slab", "none"), ("paged", "prompt_lookup")])
def test_null_injector_strict_noop(backend, draft):
    report, loop, _ = _serve(backend, draft, faults=NULL_INJECTOR)
    assert _tokens(report) == _baseline(backend, draft)
    assert not [r for r in loop.stream if r["kind"] == "fault"]
    assert report.n_injected_faults == 0 and report.n_recoveries == 0


# ---------------------------------------------------------------------------
# the chaos property: survivors are token-identical, resources leak-free,
# every injection visible in the stream
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("backend,draft",
                         [("slab", "none"), ("slab", "prompt_lookup"),
                          ("paged", "none"), ("paged", "prompt_lookup")])
def test_chaos_survivors_token_identical(backend, draft, seed):
    rates = {"step": 0.05, "prefill": 0.05, "oom": 0.03, "nan": 0.01,
             "cancel": 0.01}
    if backend == "paged":
        rates["pool"] = 0.05
    if draft != "none":
        rates["drafter"] = 0.10
    inj = FaultInjector(seed=seed, rates=rates, max_faults=8)
    report, loop, reqs = _serve(backend, draft, faults=inj,
                                max_step_retries=1, max_recoveries=20)
    base = _baseline(backend, draft)
    assert all(r.is_terminal for r in reqs)
    for i, res in enumerate(report.results):
        if res.finish_reason in ("eos", "length"):
            assert list(res.tokens) == base[i], (i, res.finish_reason)
        else:
            assert res.finish_reason in ("cancelled", "failed", "timeout")
            # partial streams never diverge before dying
            assert list(res.tokens) == base[i][:len(res.tokens)]
    _assert_pool_drained(loop)
    # the stream accounts for every single injection, 1:1
    assert len(_injected_fault_records(loop)) == len(inj.injected)
    assert report.n_injected_faults == len(inj.injected)


def test_chaos_same_seed_replays_identically():
    def once():
        inj = FaultInjector(seed=7, rates={"step": 0.1, "nan": 0.02,
                                           "pool": 0.05}, max_faults=6)
        report, _, _ = _serve("paged", "none", faults=inj,
                              max_step_retries=1, max_recoveries=20)
        return [(site, n) for site, n, _ in inj.injected], _tokens(report)
    assert once() == once()


# ---------------------------------------------------------------------------
# retry / recovery / watchdog
# ---------------------------------------------------------------------------

def test_retry_absorbs_transient_step_faults():
    inj = FaultInjector(schedule=[("step", 0), ("step", 1)])
    report, _, _ = _serve(faults=inj, max_step_retries=2)
    assert _tokens(report) == _baseline("slab", "none")
    assert report.n_retries == 2
    assert report.n_recoveries == 0


def test_recovery_rebuilds_and_replays():
    inj = FaultInjector(schedule=[("step", 1)])
    report, loop, _ = _serve(faults=inj, max_step_retries=0)
    assert _tokens(report) == _baseline("slab", "none")
    assert report.n_recoveries == 1
    kinds = [r["kind"] for r in loop.stream]
    assert "recover" in kinds
    # recovery preempted the actives: replay shows up as preempt records
    assert report.n_preemptions >= 1


def test_recovery_budget_exhausted_fails_inflight_and_returns():
    inj = FaultInjector(rates={"step": 1.0, "prefill": 1.0})
    report, loop, reqs = _serve(faults=inj, max_step_retries=0,
                                max_recoveries=2)
    # serve() RETURNED (no hang, no raise) with everything failed
    assert all(r.state is RequestState.FAILED for r in reqs)
    assert report.n_failed == len(reqs)
    assert any(r["kind"] == "degrade" and r["action"] == "abort"
               for r in loop.stream)
    _assert_pool_drained(loop)


def test_watchdog_aborts_stuck_step():
    # the budget must cover a post-recovery re-trace/re-compile of the
    # step fn, so it is generous; the injected spike is far beyond it
    inj = FaultInjector(rates={"slow": 1.0}, max_faults=1, slow_s=8.0)
    report, _, _ = _serve(faults=inj, step_timeout_s=2.5,
                          max_step_retries=0, max_recoveries=20)
    assert report.n_recoveries >= 1
    assert _tokens(report) == _baseline("slab", "none")


def test_real_executor_failure_recovers_as_step_fault(monkeypatch):
    # a genuine (non-injected) runtime failure of an executed step must be
    # wrapped and survive via the same rebuild-and-replay path
    report_ref, loop, reqs = (None, None, None)
    engine = ServingEngine(CFG, _params(), ServeConfig(max_new_tokens=8))
    loop = engine.make_loop(_requests(), n_slots=2,
                            sched_cfg=SchedulerConfig(lead_window=2))
    real_fn = loop._decode_fn
    state = {"fired": False}

    def boom(*a, **k):
        if not state["fired"]:
            state["fired"] = True
            raise jax.errors.JaxRuntimeError("simulated XLA crash")
        return real_fn(*a, **k)

    loop._decode_fn = boom
    report = loop.run()
    assert state["fired"]
    assert report.n_recoveries == 1
    assert _tokens(report) == _baseline("slab", "none")
    # the real failure shows up as a non-injected fault record
    assert any(r["kind"] == "fault" and not r.get("injected")
               and "JaxRuntimeError" in r.get("error", "")
               for r in loop.stream)


@pytest.mark.parametrize("error", [ValueError, jax.errors.JaxRuntimeError])
def test_step_compile_failure_fails_the_run(error, monkeypatch):
    """A step whose program fails to trace, lower or compile is not a
    runtime fault: no retry, no rebuild, no XLA fallback — the run fails.
    A JaxRuntimeError raised while the program is built (what a refused
    kernel compile raises) counts as a compile failure too."""
    def refuse(*a, **k):
        raise error("simulated refusal while compiling the decode step")

    monkeypatch.setattr(api, "decode_step", refuse)
    engine = ServingEngine(CFG, _params(), ServeConfig(
        max_new_tokens=8, kernel_fault_limit=1))
    engine.executor.matmul_backend = "kernel_interpret"
    loop = engine.make_loop(_requests(), n_slots=2,
                            sched_cfg=SchedulerConfig(lead_window=2))
    with pytest.raises(StepCompileError, match="simulated refusal"):
        loop.run()
    assert engine.executor.matmul_backend == "kernel_interpret"
    assert not any(r["kind"] in ("fault", "recover", "degrade", "retry")
                   for r in loop.stream)


def test_non_runtime_dispatch_error_is_not_recovered():
    """Only runtime failures of an executed step become StepFault: a plain
    host-side exception inside a dispatch propagates unrecovered."""
    engine = ServingEngine(CFG, _params(), ServeConfig(max_new_tokens=8))
    loop = engine.make_loop(_requests(), n_slots=2,
                            sched_cfg=SchedulerConfig(lead_window=2))

    def bug(*a, **k):
        raise ValueError("host-side bug")

    loop._decode_fn = bug
    with pytest.raises(ValueError, match="host-side bug"):
        loop.run()
    assert not any(r["kind"] == "recover" for r in loop.stream)


# ---------------------------------------------------------------------------
# NaN guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("draft", ["none", "prompt_lookup"])
def test_nan_guard_fails_only_the_poisoned_slot(draft):
    inj = FaultInjector(rates={"nan": 1.0}, max_faults=1)
    report, loop, _ = _serve(draft=draft, faults=inj)
    base = _baseline("slab", draft)
    failed = [r for r in report.results if r.finish_reason == "failed"]
    assert len(failed) == 1
    for i, res in enumerate(report.results):
        assert -1 not in list(res.tokens)
        if res.finish_reason == "failed":
            assert list(res.tokens) == base[i][:len(res.tokens)]
        else:
            assert list(res.tokens) == base[i]
    assert any(r["kind"] == "fault" and r.get("site") == "nan_guard"
               for r in loop.stream)
    assert loop.cm.n_active == 0


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

def test_repeated_drafter_faults_disable_speculation():
    inj = FaultInjector(rates={"drafter": 1.0})
    report, loop, _ = _serve(draft="prompt_lookup", faults=inj,
                             drafter_fault_limit=2)
    # draft-less verify steps commit the single greedy token: identity
    # against the PLAIN baseline (speculation is an optimization only)
    assert _tokens(report) == _baseline("slab", "none")
    assert loop.drafter is None
    assert any(r["kind"] == "degrade"
               and r["action"] == "disable_speculation"
               for r in loop.stream)
    assert report.n_degrades >= 1
    assert report.draft == "prompt_lookup"    # names what the run started with


def test_repeated_kernel_faults_fall_back_to_xla():
    inj = FaultInjector(schedule=[("step", 0), ("step", 2)])
    engine = ServingEngine(CFG, _params(), ServeConfig(
        max_new_tokens=8, faults=inj, max_step_retries=0,
        kernel_fault_limit=2))
    engine.executor.matmul_backend = "kernel_interpret"
    loop = engine.make_loop(_requests(), n_slots=2,
                            sched_cfg=SchedulerConfig(lead_window=2))
    report = loop.run()
    assert engine.executor.matmul_backend == "xla"
    assert any(r["kind"] == "degrade" and r["action"] == "xla_fallback"
               for r in loop.stream)
    assert _tokens(report) == _baseline("slab", "none")


def test_pool_pressure_shrinks_lead_window():
    inj = FaultInjector(rates={"pool": 0.6}, seed=3, max_faults=12)
    report, loop, _ = _serve("paged", faults=inj, max_step_retries=1,
                             max_recoveries=20, lead_window=4,
                             pool_pressure_limit=2)
    assert report.n_preemptions >= 2
    assert loop.sched.cfg.lead_window < 4
    assert any(r["kind"] == "degrade"
               and r["action"] == "shrink_lead_window"
               for r in loop.stream)
    assert _tokens(report) == _baseline("paged", "none")
    _assert_pool_drained(loop)


# ---------------------------------------------------------------------------
# cancellation + deadlines
# ---------------------------------------------------------------------------

def test_cancel_before_run_never_admits_the_request():
    engine = ServingEngine(CFG, _params(), ServeConfig(max_new_tokens=8))
    reqs = _requests()
    engine.cancel(reqs[3].request_id)
    loop = engine.make_loop(reqs, n_slots=2,
                            sched_cfg=SchedulerConfig(lead_window=2))
    report = loop.run()
    base = _baseline("slab", "none")
    assert reqs[3].state is RequestState.CANCELLED
    assert reqs[3].finish_reason == "cancelled"
    assert list(report.results[3].tokens) == []
    for i in (0, 1, 2, 4):
        assert list(report.results[i].tokens) == base[i]
    assert report.n_cancelled == 1
    recs = [r for r in loop.stream if r["kind"] == "cancel"]
    assert len(recs) == 1
    assert recs[0]["request_id"] == reqs[3].request_id


@pytest.mark.parametrize("backend", ["slab", "paged"])
def test_cancel_mid_decode_releases_all_blocks(backend):
    engine = ServingEngine(CFG, _params(), ServeConfig(
        max_new_tokens=8, cache_backend=backend, block_size=4))
    reqs = _requests()
    target = reqs[0]

    def hook(loop):
        if any(r is target for r in loop.active.values()):
            engine.cancel(target.request_id)

    loop = engine.make_loop(reqs, n_slots=2,
                            sched_cfg=SchedulerConfig(lead_window=2))
    loop.on_step_end = hook
    report = loop.run()
    base = _baseline(backend, "none")
    assert target.state is RequestState.CANCELLED
    assert 0 < len(report.results[0].tokens) < len(base[0])
    assert list(report.results[0].tokens) == base[0][:len(
        report.results[0].tokens)]
    for i in (1, 2, 3, 4):
        assert list(report.results[i].tokens) == base[i]
    assert loop.cm.n_active == 0
    _assert_pool_drained(loop)
    recs = [r for r in loop.stream if r["kind"] == "cancel"]
    assert [r["request_id"] for r in recs] == [target.request_id]
    assert recs[0]["where"] == "active"


def test_ttft_deadline_expires_waiting_request():
    engine = ServingEngine(CFG, _params(), ServeConfig(max_new_tokens=8))
    reqs = _requests()
    reqs[4].ttft_deadline_s = 0.0   # expires the moment it is submitted
    loop = engine.make_loop(reqs, n_slots=2,
                            sched_cfg=SchedulerConfig(lead_window=2))
    report = loop.run()
    assert reqs[4].state is RequestState.TIMED_OUT
    assert reqs[4].finish_reason == "timeout"
    assert report.n_timed_out == 1
    recs = [r for r in loop.stream if r["kind"] == "timeout"]
    assert len(recs) == 1 and recs[0]["deadline"] == "ttft"
    base = _baseline("slab", "none")
    for i in range(4):
        assert list(report.results[i].tokens) == base[i]


def test_total_deadline_expires_active_request():
    engine = ServingEngine(CFG, _params(), ServeConfig(
        max_new_tokens=8, cache_backend="paged", block_size=4))
    reqs = _requests()
    target = reqs[0]

    def hook(loop):
        if any(r is target for r in loop.active.values()):
            target.deadline_s = 0.0
            loop._any_deadlines = True

    loop = engine.make_loop(reqs, n_slots=2,
                            sched_cfg=SchedulerConfig(lead_window=2))
    loop.on_step_end = hook
    report = loop.run()
    assert target.state is RequestState.TIMED_OUT
    recs = [r for r in loop.stream if r["kind"] == "timeout"]
    assert recs and recs[0]["where"] == "active"
    assert recs[0]["deadline"] == "total"
    _assert_pool_drained(loop)


# ---------------------------------------------------------------------------
# rejection path (satellite): both rejection flavors emit exactly one
# reject record through the one central RequestQueue.reject funnel
# ---------------------------------------------------------------------------

def test_on_reject_emits_exactly_one_record_per_path():
    engine = ServingEngine(CFG, _params(), ServeConfig(max_new_tokens=8))
    ok = Request(prompt=_prompt(4, 1), max_new_tokens=2)
    over_capacity = Request(prompt=_prompt(4, 2), max_new_tokens=2)
    too_big = Request(prompt=_prompt(4, 3), max_new_tokens=64)
    loop = engine.make_loop([ok, over_capacity, too_big], n_slots=2,
                            cache_T=8,
                            sched_cfg=SchedulerConfig(max_waiting=1))
    report = loop.run()
    assert over_capacity.finish_reason == "rejected"
    assert too_big.finish_reason == "rejected"
    assert ok.finish_reason in ("eos", "length")
    recs = [r for r in loop.stream if r["kind"] == "reject"]
    assert sorted(r["request_id"] for r in recs) == sorted(
        [over_capacity.request_id, too_big.request_id])
    assert report.n_rejected == 2


# ---------------------------------------------------------------------------
# injector unit behavior (no jax)
# ---------------------------------------------------------------------------

def test_injector_schedule_and_ledger():
    inj = FaultInjector(schedule=[("step", 1)], rates={})
    assert not inj.fire("step")
    assert inj.fire("step")
    assert not inj.fire("step")
    assert [(s, n) for s, n, _ in inj.injected] == [("step", 1)]


def test_injector_max_faults_cap():
    inj = FaultInjector(rates={"step": 1.0}, max_faults=2)
    fires = [inj.fire("step") for _ in range(5)]
    assert fires == [True, True, False, False, False]


def test_injector_cancel_requests_dedups():
    inj = FaultInjector(rates={"cancel": 1.0})
    assert inj.cancel_requests([1, 2]) == [1, 2]
    assert inj.cancel_requests([1, 2, 3]) == [3]


def test_null_injector_has_no_side_effects():
    ledger0 = list(NULL_INJECTOR.injected)
    assert not NULL_INJECTOR.fire("step")
    NULL_INJECTOR.check("oom")
    NULL_INJECTOR.delay()
    assert NULL_INJECTOR.nan_slots([0, 1]) == []
    assert NULL_INJECTOR.cancel_requests([1]) == []
    assert list(NULL_INJECTOR.injected) == ledger0 == []


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(seed=st.integers(0, 2**16),
           rates=st.dictionaries(
               st.sampled_from(["step", "pool", "nan", "oom"]),
               st.floats(0.0, 1.0), max_size=4),
           n_checks=st.integers(0, 64))
    def test_injector_deterministic_replay(seed, rates, n_checks):
        """Property: a given (seed, rates, call sequence) replays the
        exact same fault schedule."""
        def trace():
            inj = FaultInjector(seed=seed, rates=rates)
            return [inj.fire(site) for site in
                    ["step", "pool", "nan", "oom"] * n_checks]
        assert trace() == trace()
