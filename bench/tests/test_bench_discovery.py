"""Entries are found from files alone; the harness refuses what it cannot
measure."""

import json
import os
import subprocess
import sys
import time

import pytest

import tiny
from bench import peaks, spec, traffic


def test_new_entries_need_only_new_files(tmp_path):
    root = tiny.make_root(tmp_path)
    d = os.path.join(root, "bench")
    assert "steps_seen" not in spec.metric_readers(root)
    # a configuration, a mix, a cell and a per-layer metric, as files
    with open(os.path.join(d, "configs", "tiny2.json"), "w") as f:
        json.dump(dict(tiny.CONF, num_hidden_layers=3), f)
    with open(os.path.join(d, "traffic", "bursty.json"), "w") as f:
        json.dump(dict(tiny.MIX, output_len={"dist": "lognormal", "median": 5,
                                             "sigma": 0.1, "min": 5,
                                             "max": 5}), f)
    with open(os.path.join(d, "workloads", "tiny2.bursty.json"), "w") as f:
        json.dump(dict(tiny.CELL, config="tiny2", traffic="bursty"), f)
    with open(os.path.join(d, "metrics", "steps_seen.py"), "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx['steps'])) or None\n")
    bm = spec.benchmark(root)
    bm["configs"].append({"name": "tiny2", "source": "test", "reduced": [],
                          "file": "bench/configs/tiny2.json", "why": "t"})
    bm["workloads"].append({"name": "tiny2.bursty", "config": "tiny2",
                            "traffic": "bursty", "chips": 1, "why": "t"})
    bm["per_layer"].append({"name": "steps_seen", "unit": "1",
                            "better": "higher", "source": "program_span",
                            "layer": "serve loop", "moves": "itl_p99_ms"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)

    sp = spec.cell(root, "tiny2.bursty")
    assert sp["conf"]["num_hidden_layers"] == 3
    sched = traffic.schedule(sp["mix"], sp["cell"]["rate_rps"], 2.0, 9, 512)
    assert {r["max_new_tokens"] for r in sched} == {5}
    readers = spec.metric_readers(root)
    assert readers["steps_seen"]({"steps": [1, 2]}) == 2.0
    assert readers["steps_seen"]({"steps": []}) is None
    names = [m["name"] for m in spec.cell_metrics(bm, True)]
    assert "steps_seen" in names


def test_metric_workloads_pick_the_cells():
    bm = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}],
          "per_layer": [{"name": "c", "workloads": ["y"]}, {"name": "d"}]}
    names = lambda trace, w: [m["name"] for m in
                              spec.cell_metrics(bm, trace, w)]
    assert names(False, "x") == ["a", "b"]
    assert names(False, "y") == ["a"]
    assert names(True, "x") == ["d"]
    assert names(True, "y") == ["c", "d"]
    assert names(False, None) == ["a", "b"]


def test_repo_metrics_name_cells_that_report_what_they_move():
    """A metric's ``workloads`` are cells of the benchmark, and a per-layer
    metric is read only in cells that report the end-to-end metric it
    moves; every cell reports ``setup_s``, another end-to-end metric and a
    per-layer metric."""
    bm = spec.benchmark(tiny.REPO)
    cells = [w["name"] for w in bm["workloads"]]
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)
    for w in cells:
        e2e = {m["name"] for m in spec.cell_metrics(bm, False, w)}
        layer = spec.cell_metrics(bm, True, w)
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        assert all(m["moves"] in e2e for m in layer), w


def test_repo_benchmark_names_resolve():
    root = tiny.REPO
    bm = spec.benchmark(root)
    for w in bm["workloads"]:
        sp = spec.cell(root, w["name"])
        assert sp["cell"]["config"] == w["config"]
        assert sp["cell"]["traffic"] == w["traffic"]
    assert {m["name"] for m in bm["per_layer"]} <= set(
        spec.metric_readers(root))


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**33 + 5])
def test_seeds_share_the_work_and_draw_the_tokens(seed):
    mix = tiny.MIX
    a = traffic.schedule(mix, 6.0, 5.0, seed, 512)
    b = traffic.schedule(mix, 6.0, 5.0, seed + 1, 512)
    assert a == traffic.schedule(mix, 6.0, 5.0, seed, 512)
    work = lambda s: [(r["due"], len(r["prompt"]), r["max_new_tokens"])
                      for r in s]
    assert work(a) == work(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    due = [r for r in a if r["due"] < 5.0]
    assert len(due) == 30 and due[-1]["due"] < 5.0
    assert all(2 <= t < 512 for r in a for t in r["prompt"])
    assert min(r["due"] for r in a if r["due"] >= 5.0) > 5.0


def test_unknown_device_kind_is_an_error():
    assert peaks.lookup("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("TPU v9 imaginary")


def test_run_exits_nonzero_without_a_tpu_before_building():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t = time.monotonic()
    p = subprocess.run([sys.executable, os.path.join(tiny.BENCH, "run.py"),
                        "--workload", "qwen2-1.5b.longprompt", "--seed", "1",
                        "--seconds", "10", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120,
                       cwd=tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr
    # a full-width build would take minutes on the CPU; refusing takes none
    assert time.monotonic() - t < 60


def test_run_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ cannot run."""
    import shutil
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2-1.5b.chat", "--seed", "1", "--seconds", "10",
                        "--trace", "0"], capture_output=True, text=True,
                       env=dict(env, JAX_PLATFORMS="cpu"), timeout=120,
                       cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_cell_pools_follow_their_memory_arithmetic():
    """Each cell's pool is the chip's memory budget less weights and a
    reserve, in whole blocks, and no slot count exceeds what that pool
    holds at the mix's longest request."""
    root = tiny.REPO
    for w in spec.benchmark(root)["workloads"]:
        sp = spec.cell(root, w["name"])
        mem, sv = sp["cell"]["memory"], sp["cell"]["serve"]
        lim = traffic.max_lengths(sp["mix"])
        conf = sp["conf"]
        kv = (2 * conf["num_hidden_layers"] * conf["num_key_value_heads"]
              * (conf["hidden_size"] // conf["num_attention_heads"]) * 2)
        assert kv == conf["memory"]["kv_bytes_per_token"]
        assert mem["block_bytes"] == kv * sv["block_size"]
        assert mem["budget_bytes"] == int(mem["utilization"]
                                          * mem["hbm_bytes"])
        assert mem["weight_bytes"] == (
            conf["memory"]["int8_layer_weight_bytes"]
            + conf["memory"]["embedding_bf16_bytes"])
        assert sv["num_blocks"] == mem["num_blocks"] == (
            mem["budget_bytes"] - mem["weight_bytes"]
            - mem["reserve_bytes"]) // (mem["pool_copies"]
                                        * mem["block_bytes"])
        assert mem["cache_T"] == lim["prompt"] + lim["output"] + 8
        assert mem["blocks_per_seq"] == -(-mem["cache_T"]
                                          // sv["block_size"])
        assert mem["n_slots_memory"] == ((mem["num_blocks"] - 1)
                                         // mem["blocks_per_seq"])
        assert 1 <= sv["n_slots"] <= mem["n_slots_memory"]
