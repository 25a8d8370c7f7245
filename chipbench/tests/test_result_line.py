"""The result line as a run prints it, built from recorded run output (a
mamba2-train run and a traced hymba-serve-decode run on one TPU v5e, in
``data/recorded-lines.json``): the last line of stdout carries the
result's keys and, last, the numbers compared with their limits; timing
diagnostics go on an earlier line; the compared numbers are also the last
lines of stderr."""
import json
from pathlib import Path

import pytest

import harness

RECORDED = json.loads((Path(__file__).parent / "data" / "recorded-lines.json")
                      .read_text())["lines"]
REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


def recorded(name):
    """(result, checks, timing) as a ``drive_<kind>.run`` returns them."""
    line = dict(RECORDED[name])
    checks, timing = line.pop("checks"), line.pop("timing")
    return line, checks, timing


@pytest.mark.parametrize("name, keys", [
    ("mamba2-train", REQUIRED + ["checks"]),
    ("hymba-serve-decode.traced", REQUIRED + ["breakdown", "checks"]),
])
def test_last_line_holds_the_result_keys_then_checks(name, keys):
    result, checks, _ = recorded(name)
    line = harness.result_line(result, checks)
    assert list(line) == keys
    assert line["checks"] == checks
    for key in keys[:-1]:
        assert line[key] == result[key]


def test_timing_goes_on_an_earlier_line(capsys):
    result, checks, timing = recorded("mamba2-train")
    harness.emit(dict(result, timing=timing), checks, timing)
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0]) == {"timing": timing}
    assert list(json.loads(lines[-1])) == REQUIRED + ["checks"]
    assert err.strip().splitlines()[-2:] == [
        f"check {name}: {c['value']!r} (limit {c['limit']!r})"
        for name, c in checks.items()]
