"""Shared benchmark plumbing: artifact dir, timing, run-metadata stamping.

Every BENCH_*.json artifact written through :func:`save_artifact` carries a
``_meta`` block (git sha, jax version, device kind/count, hostname, UTC
timestamp, artifact schema version).  ``benchmarks/compare.py`` — the CI
regression gate — uses it to refuse cross-machine comparisons instead of
reporting hardware differences as regressions.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import time

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                            "bench")

#: Version of the BENCH_*.json envelope (the ``_meta`` block and how metric
#: keys are named).  Bump when compare.py's parsing assumptions change.
ARTIFACT_SCHEMA_VERSION = 1


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def run_metadata() -> dict:
    """Provenance stamped into every artifact.  jax imports lazily so
    host-only scripts (and compare.py itself) stay import-light."""
    meta = {
        "schema": ARTIFACT_SCHEMA_VERSION,
        "git_sha": _git_sha(),
        "hostname": socket.gethostname(),
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    try:
        import jax
        devices = jax.devices()
        meta["jax_version"] = jax.__version__
        meta["device_kind"] = devices[0].device_kind if devices else "none"
        meta["device_count"] = len(devices)
    except Exception:
        meta["jax_version"] = "unavailable"
        meta["device_kind"] = "unknown"
        meta["device_count"] = 0
    return meta


def holds_accelerator() -> bool:
    """True when this process's JAX runs on an accelerator.  The process
    then holds its chips and a child process cannot open them, so
    multi-device legs run here over ``jax.devices()``; only virtual CPU
    devices (which need ``XLA_FLAGS`` before JAX starts) take a worker
    subprocess."""
    import jax
    return jax.default_backend() != "cpu"


def save_artifact(name: str, payload) -> str:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    if isinstance(payload, dict) and "_meta" not in payload:
        payload = dict(payload, _meta=run_metadata())
    path = os.path.join(ARTIFACT_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6
