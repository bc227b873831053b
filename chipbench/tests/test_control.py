"""The float8 control, at toy size on the CPU: the limit in the toy
configuration passes the program and fails the control on three seeds
(on the chip, at the cells' sizes, ``control.py`` makes the same reading
over a dozen seeds)."""
import json

import control
import run


def test_control_fails_where_the_program_passes(tiny_root, monkeypatch,
                                                capsys):
    root = tiny_root("tiny_dense", "tiny.closed")
    cell, real = run.load_cell, run.run_cell
    monkeypatch.setattr(run, "load_cell",
                        lambda name, r=root: cell(name, r))
    monkeypatch.setattr(run, "run_cell", lambda *a, **k: real(
        *a, root=root, require_tpu=False, **k))
    assert control.main(["--workload", "tiny.cell", "--seconds", "2",
                         "--seeds", "3,4,2147483659"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limit = run.load_cell("tiny.cell")["config"]["correct"]["max_logit_gap"]
    gap = summary["max_logit_gap"]
    assert summary["seeds"] == 3
    assert gap["program_max"] <= limit < gap["control_min"]
