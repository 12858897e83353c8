"""The float8 control, put in the program's place, reads above the
limit that the program keeps, by the same decision that a run prints as
``correct`` (the chip readings are in PERF.md)."""

import json

from bench import calibrate
from bench.tests import tiny


def test_control_fails_where_the_program_passes(tmp_path, capsys):
    bench = tiny.write(tmp_path)
    assert calibrate.main(["--workload", "tiny.tinydoc", "--seeds", "1,2,3",
                           "--control-seeds", "1,2,3", "--seconds", "4",
                           "--benchmark", str(bench), "--dry-run"]) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    limit = tiny.LIMITS["max_logit_gap"]
    assert len(rows) == 3
    assert all(r["program"]["correct"] for r in rows)
    assert max(r["program"]["max_logit_gap"] for r in rows) <= limit
    assert not any(r["control"]["correct"] for r in rows)
    assert min(r["control"]["max_logit_gap"] for r in rows) > limit


def test_a_control_run_reads_not_correct(tmp_path, capsys):
    res = tiny.run(capsys, tiny.write(tmp_path), seed=2, extra=["--control"])
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > tiny.LIMITS["max_logit_gap"]
