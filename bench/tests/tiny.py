"""A tiny cell for CPU tests: a temporary checkout root holding its own
BENCHMARK.json and bench/ data files (configuration, mix, cell), with the
real metric readers copied in.  The harness code is the real one."""

from __future__ import annotations

import copy
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONF = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "serving": {"arch": "tiny", "family": "dense", "qkv_bias": True,
                "ffn_type": "swiglu", "matmul_mode": "bp_exact"},
    "source": "test", "reduced": [],
}
MIX = {
    "arrivals": {"process": "poisson"},
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 9, "max": 16},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 4, "max": 16},
    "base_seed": 0,
}
CELL = {
    "config": "tiny", "traffic": "small", "rate_rps": 6.0,
    "serve": {"n_slots": 4, "block_size": 8, "num_blocks": None,
              "max_prefill_batch": 2, "lead_window": 2, "max_waiting": 64},
    "trace_s": 1.0,
    "check": {"n_requests": 3, "widest_logit_gap_limit": 0.5},
}
PEAKS = {"int8_ops": 1e12, "bf16_flops": 5e11, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e9}


def make_root(tmp, *, conf=None, mix=None, cell=None, chips=1) -> str:
    root = str(tmp)
    d = os.path.join(root, "bench")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(d, sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(d, "metrics"), dirs_exist_ok=True)
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bm = copy.deepcopy(real)
    bm["configs"] = [{"name": "tiny", "source": "test",
                      "file": "bench/configs/tiny.json", "reduced": [],
                      "why": "test"}]
    bm["workloads"] = [{"name": "tiny.small", "config": "tiny",
                        "traffic": "small", "chips": chips, "why": "test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)      # the tiny cell reports every metric
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    for path, obj in ((("configs", "tiny.json"), conf or CONF),
                      (("traffic", "small.json"), mix or MIX),
                      (("workloads", "tiny.small.json"), cell or CELL)):
        with open(os.path.join(d, *path), "w") as f:
            json.dump(obj, f)
    return root


def on_cpu(monkeypatch):
    """Steer the harness past its look for a chip, onto the CPU."""
    import jax
    from bench import harness
    monkeypatch.setattr(harness, "require_accelerator",
                        lambda chips: jax.devices())
    monkeypatch.setattr(harness, "lookup_peaks", lambda kind: PEAKS)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "")
