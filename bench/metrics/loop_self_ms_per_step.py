"""Serve loop: host time per decode step spent in the loop's named
phases, from its own stamps.  A step record's ``phases`` partition the
time since the previous step record: device dispatch (``dispatch_s``),
the named host phases (``inbox_s``, ``sweep_s``, ``plan_s``, ``stage_s``,
``prepare_s``, ``install_s``, ``commit_s``, ``notify_s``, ``idle_s``...)
and ``other_s``, the time outside every named phase.  Over the window's
records after the first, the named host phases are summed; the first adds
only its own host time (``wall_s`` less its dispatch), since its earlier
phases lie before the window's first step.  Over the decode steps.  What
``host_ms_per_step`` holds beyond this is ``other_s``.  None where the
records carry no loop phases.  Moves ``itl_p99_ms``."""

NOT_HOST = ("dispatch_s", "other_s")


def read(ctx):
    steps = sorted(ctx["steps"], key=lambda s: s["ts_s"])
    n_decode = sum(1 for s in steps if s["kind"] == "decode")
    if not n_decode or any("other_s" not in s["phases"] for s in steps):
        return None
    first = steps[0]
    own = first["wall_s"] - first["phases"]["dispatch_s"]
    own += sum(v for s in steps[1:] for k, v in s["phases"].items()
               if k not in NOT_HOST)
    return 1e3 * own / n_decode
