"""The members of the program the harness reaches past its public surface.

Warm-up drives ``ServeLoop`` through ``_drain_inbox``, ``sweep``,
``_step``, ``arrivals``, ``rq`` and ``active`` before the replica's worker
starts; step records are put on the host clock through
``ServeLoop._wall0``; ``CompileCounter`` listens for JAX's backend-compile
event.  A change to any of them must fail here, not shift the yardstick
unseen: these tests check what the harness relies on, not only the names.
"""

import time

import numpy as np

import tiny
from bench import harness, spec, traffic


def _server(tmp_path):
    root = tiny.make_root(tmp_path)
    sp = spec.cell(root, "tiny.small")
    from bench import weights
    params = weights.build(sp["conf"], 5)
    return sp, harness.Server(sp, params, trace=False)


def test_warm_up_reaches_every_prefill_shape_and_drains(tmp_path):
    sp, server = _server(tmp_path)
    a = time.monotonic()
    n = server.warm(11)
    b = time.monotonic()
    loop = server.loop
    assert not (loop.arrivals or len(loop.rq) or loop.active)
    gmax = sp["cell"]["serve"]["max_prefill_batch"]
    buckets = harness.prefill_buckets(int(sp["mix"]["prompt_len"]["min"]),
                                      int(sp["mix"]["prompt_len"]["max"]),
                                      loop.cm.cache_T)
    want = {(p, g) for p in buckets for g in range(1, gmax + 1)}
    steps = server.step_records(a, b)
    have = {(s["pad_to"], s["group_size"]) for s in steps
            if s["kind"] == "prefill"}
    assert have == want
    assert n == len(buckets) * gmax * (gmax + 1) // 2
    assert any(s["kind"] == "decode" for s in steps)
    # every record of the warm-up lies on the host clock inside [a, b]
    assert len(steps) == sum(1 for r in loop.stream
                             if r["kind"] in ("prefill", "decode"))
    for s in steps:
        assert a <= s["t0"] <= s["t1"] <= b
    server.free()


def test_step_record_clock_is_the_host_monotonic_clock(tmp_path):
    _, server = _server(tmp_path)
    loop = server.loop
    assert isinstance(loop._wall0, float)
    before = time.monotonic()
    rec = loop._emit("bench_probe")
    after = time.monotonic()
    assert before <= loop._wall0 + rec["ts_s"] <= after
    server.free()


def test_compile_counter_counts_a_fresh_program():
    import jax
    import jax.numpy as jnp
    counter = harness.CompileCounter()
    a = time.monotonic()
    salt = float(np.random.default_rng().integers(1 << 30))
    jax.jit(lambda x: x * 3.0 + salt)(jnp.ones(7)).block_until_ready()
    assert counter.between(a, time.monotonic()) >= 1


def test_warm_up_lengths_are_the_mix_buckets():
    """The warm-up's prompt lengths follow the program's bucket rule over
    the mix's whole prompt range."""
    mix = tiny.MIX
    lim = traffic.max_lengths(mix)
    cache_T = lim["prompt"] + lim["output"] + 8
    b = harness.prefill_buckets(mix["prompt_len"]["min"],
                                mix["prompt_len"]["max"], cache_T)
    from repro.serving.scheduler import prefill_bucket_len
    for n in range(mix["prompt_len"]["min"], mix["prompt_len"]["max"] + 1):
        assert prefill_bucket_len(n, cache_T) in b
