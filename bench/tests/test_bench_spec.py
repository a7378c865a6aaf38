"""BENCHMARK.json keeps the contract's shape, every piece of every cell is
found by name, a new cell needs no edit of a file that exists, and the
command refuses a host without a TPU."""
import copy
import io
import json
import os
import re
from contextlib import redirect_stdout

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = harness.benchmark_spec()


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_is_found_by_name(w):
    cell = harness.find_cell(w["name"])
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.metric_reader(m["name"]))
    for m in cell.end_to_end:
        if m["name"] != "setup_s":
            assert callable(harness.metric_reader(m["name"]))
    assert cell.traffic["kind"] in ("serve", "train")
    assert cell.limits["limits"]


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(c):
    f = harness.load_json(harness.ROOT, c["file"])
    assert f["name"] == c["name"] and f["source"] == c["source"]
    assert f["reduced"] == c["reduced"]
    assert set(f["published"]) == set(c["reduced"])
    for module in (f["reference"], f["arch"]):
        assert os.path.exists(os.path.join(harness.BENCH, "configs",
                                           module + ".py"))
    cfg = harness.arch_module(f).model_config(harness.as_run(f))
    assert len(cfg.pattern) == f["num_hidden_layers"]


def test_a_new_cell_is_one_new_entry(tmp_path, monkeypatch):
    """A cell from an existing configuration and traffic mix is one more
    ``workloads`` entry and its limits file: no existing file changes."""
    import shutil
    for d in ("traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(harness.BENCH, d), tmp_path / d)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    spec = copy.deepcopy(SPEC)
    base = spec["workloads"][0]
    spec["workloads"].append(dict(base, name="new.cell"))
    (tmp_path / "limits" / "new.cell.json").write_text(
        json.dumps(harness.load_json(harness.BENCH, "limits",
                                     base["name"] + ".json")))
    monkeypatch.setattr(harness, "BENCH", str(tmp_path))
    cell = harness.find_cell("new.cell", spec)
    old = harness.find_cell(base["name"], spec)
    assert (cell.config, cell.traffic, cell.limits) == (
        old.config, old.traffic, old.limits)
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in old.per_layer if "workloads" not in m]
    assert all(p.read_bytes() == b for p, b in before.items())


def test_peaks_table_refuses_an_unknown_chip():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_command_refuses_a_host_without_tpu(capsys):
    from bench import run
    name = SPEC["workloads"][0]["name"]
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", name, "--seed", str(2 ** 33 + 3),
                       "--seconds", "1", "--trace", "0"])
    assert rc == 2 and buf.getvalue() == ""
    assert "no TPU" in capsys.readouterr().err


def test_judge_fails_a_missing_or_large_reading():
    ok, rows = harness.judge({"a": 0.1, "b": float("nan")},
                             {"a": 0.2, "b": 1.0, "c": 1.0})
    assert not ok
    assert rows == [["a", 0.1, 0.2], ["b", rows[1][1], 1.0],
                    ["c", None, 1.0]]
    assert harness.judge({"a": 0.1}, {"a": 0.2})[0]


def test_emit_puts_checks_last(capsys):
    harness.emit({"correct": True, "attempted": 1, "failed": 0,
                  "metrics": {}, "device": {}}, [["gap", 0.5, 1.0]])
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"gap": {"value": 0.5, "limit": 1.0}}
    assert cap.err.strip().splitlines()[-1] == "check gap = 0.5 limit 1.0"
