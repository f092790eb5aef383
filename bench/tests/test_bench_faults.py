"""With the timed path broken underneath, a run comes out not correct:
a token altered where it is produced, and a decode step that leaves its
state (the KV cache) unchanged."""

import json

import tiny
from bench import harness


def run(tmp_path, monkeypatch, capsys, seed):
    root = tiny.make_root(tmp_path)
    tiny.on_cpu(monkeypatch)
    rc = harness.main(["--workload", "tiny.small", "--seed", str(seed),
                       "--seconds", "3", "--trace", "0"], root, 0.0)
    out, _ = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1])


def test_altered_token_is_not_correct(tmp_path, monkeypatch, capsys):
    from repro.serving.engine import ServeLoop
    orig = ServeLoop._append_token

    def altered(self, req, tok, wall):
        if len(req.tokens) == 2:       # the third token, from a decode step
            tok = (tok + 1) % 512
        return orig(self, req, tok, wall)

    monkeypatch.setattr(ServeLoop, "_append_token", altered)
    res = run(tmp_path, monkeypatch, capsys, 5150)
    c = res["checks"]["widest_logit_gap"]
    assert res["correct"] is False and c["value"] > c["limit"]


def test_unchanged_decode_state_is_not_correct(tmp_path, monkeypatch,
                                               capsys):
    from repro.models import attention
    monkeypatch.setattr(attention, "paged_write_kv",
                        lambda c, new, blk, off: c)
    res = run(tmp_path, monkeypatch, capsys, 5151)
    c = res["checks"]["widest_logit_gap"]
    assert res["correct"] is False and c["value"] > c["limit"]
