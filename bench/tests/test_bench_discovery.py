"""Entries are found from files alone; the harness refuses what it cannot
measure."""

import copy
import json
import os
import subprocess
import sys
import time

import pytest

import tiny
from bench import harness, peaks, spec, traffic, weights


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


# A copy of the default reference that notes each use of its functions in
# a file beside it: proof that the harness took the module the
# configuration names, and took its layout.
NOTING_REFERENCE = """

_served_gaps = served_gaps


def _note(what):
    with open(__file__ + ".calls", "a") as f:
        f.write(what + "\\n")


def layout(conf):
    from bench import weights
    _note("layout")
    return weights.layout(conf)


def served_gaps(*args, **kw):
    _note("served_gaps")
    return _served_gaps(*args, **kw)
"""


def test_layout_mesh_and_reference_need_only_new_files(tmp_path, monkeypatch,
                                                       capsys):
    """A configuration without QKV bias that names its own reference
    module, and a cell that names a mesh, run from new files alone."""
    root = tiny.make_root(tmp_path)
    d = os.path.join(root, "bench")
    ref_path = os.path.join(d, "configs", "nobias_reference.py")
    with open(os.path.join(tiny.BENCH, "reference.py")) as f:
        src = f.read()
    with open(ref_path, "w") as f:
        f.write(src + NOTING_REFERENCE)
    conf = dict(tiny.CONF, serving=dict(tiny.CONF["serving"], qkv_bias=False),
                reference_file="bench/configs/nobias_reference.py")
    cell = copy.deepcopy(tiny.CELL)
    cell.update(config="nobias")
    cell["serve"]["mesh_shape"] = [1, 1]
    with open(os.path.join(d, "configs", "nobias.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(d, "workloads", "nobias.small.json"), "w") as f:
        json.dump(cell, f)
    bm = spec.benchmark(root)
    bm["configs"].append({"name": "nobias", "source": "test", "reduced": [],
                          "file": "bench/configs/nobias.json", "why": "t"})
    bm["workloads"].append({"name": "nobias.small", "config": "nobias",
                            "traffic": "small", "chips": 1, "why": "t"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)

    from repro.serving.executor import MeshExecutor
    meshes = []
    init = MeshExecutor.__init__

    def noting_init(self, *a, **kw):
        meshes.append(kw["mesh"].devices.shape)
        init(self, *a, **kw)

    monkeypatch.setattr(MeshExecutor, "__init__", noting_init)
    tiny.on_cpu(monkeypatch)
    rc = harness.main(["--workload", "nobias.small", "--seed", "2718281828",
                       "--seconds", "3", "--trace", "0"], root, 0.0)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert meshes == [(1, 1)]
    with open(ref_path + ".calls") as f:
        calls = f.read().split()
    assert calls[0] == "layout" and "served_gaps" in calls
    shapes = spec.reference(root, conf).layout(conf)
    assert "b" not in shapes["layers"]["attn"]["wq"]


@pytest.mark.parametrize("shape,chips", [([2, 2], 1), ([1, 2], 4),
                                         ([4], 4), ([0, 4], 4)])
def test_mesh_off_the_cell_chips_is_refused_before_building(
        tmp_path, monkeypatch, shape, chips):
    cell = copy.deepcopy(tiny.CELL)
    cell["serve"]["mesh_shape"] = shape
    root = tiny.make_root(tmp_path, cell=cell, chips=chips)

    def built(*_a, **_kw):
        raise AssertionError("the harness went on to build")

    for name in ("enable_compile_cache", "require_accelerator",
                 "served_model", "draw_weights"):
        monkeypatch.setattr(harness, name, built)
    with pytest.raises(ValueError, match="mesh_shape"):
        harness.run_cell(root, "tiny.small", 1, 3.0, False, 0.0)


def test_arch_config_forwards_the_serving_fields():
    from repro.configs.base import ArchConfig
    conf = spec.load_json(os.path.join(tiny.BENCH, "configs",
                                       "qwen2-1.5b.json"))
    # the keys read before keep their meaning
    assert harness.arch_config(conf, "qwen2-1.5b") == ArchConfig(
        name="qwen2-1.5b", family="dense", num_layers=28, d_model=1536,
        num_heads=12, num_kv_heads=2, d_ff=8960, vocab_size=151936,
        head_dim=128, qkv_bias=True, ffn_type="swiglu", rope_theta=1e6,
        tie_embeddings=True, norm_eps=1e-6, matmul_mode="bp_exact",
        matmul_backend="auto")
    sv = dict(tiny.CONF["serving"], kv_cache_int8=True, num_experts=4,
              top_k=2, mrope_sections=[2, 3, 3])
    cfg = harness.arch_config(dict(tiny.CONF, serving=sv), "t")
    assert (cfg.kv_cache_int8, cfg.num_experts, cfg.top_k,
            cfg.mrope_sections) == (True, 4, 2, (2, 3, 3))
    for key in ("name", "matmul_backend", "num_layers"):
        with pytest.raises(ValueError, match=key):
            harness.arch_config(dict(tiny.CONF, serving=dict(sv, **{key: 1})),
                                "t")


def test_layout_follows_qkv_bias_and_draws_leaves_by_a_given_rule():
    import jax
    import jax.numpy as jnp
    nobias = dict(tiny.CONF, serving=dict(tiny.CONF["serving"],
                                          qkv_bias=False))
    attn = weights.layout(nobias)["layers"]["attn"]
    assert all("b" not in attn[w] for w in attn)
    attn = weights.layout(tiny.CONF)["layers"]["attn"]
    assert ["b" in attn[w] for w in ("wq", "wk", "wv", "wo")] == [
        True, True, True, False]
    harness.check_layout(weights.layout(nobias),
                         harness.arch_config(nobias, "t"))
    with pytest.raises(ValueError, match="layout"):
        harness.check_layout(weights.layout(tiny.CONF),
                             harness.arch_config(nobias, "t"))
    shapes = dict(weights.layout(tiny.CONF),
                  gate={"alpha": jax.ShapeDtypeStruct((3,), jnp.float32)})
    with pytest.raises(ValueError, match="gate/alpha"):
        weights.build(tiny.CONF, 1, shapes=shapes)
    drawn = weights.build(
        tiny.CONF, 1, shapes=shapes,
        draw=lambda key, path, sds, at: jnp.full(sds.shape, 7.0, sds.dtype))
    assert drawn["gate"]["alpha"].tolist() == [7.0, 7.0, 7.0]


def test_reference_file_stays_inside_the_checkout(tmp_path):
    for path in ("/abs/ref.py", "bench/../../ref.py"):
        with pytest.raises(ValueError, match="inside the checkout"):
            spec.reference(str(tmp_path), {"reference_file": path})
    from bench import reference
    assert spec.reference(str(tmp_path), {}) is reference


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
