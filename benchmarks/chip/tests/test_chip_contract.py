"""BENCHMARK.json against the rules the harness relies on, and the command
refusing to run anywhere but on a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip.tests import small
from benchmarks.chip.tests.small import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CHIP = ROOT / "benchmarks" / "chip"


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _line_ok(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


@pytest.mark.parametrize("pending", [False, True],
                         ids=["BENCHMARK", "with-pending"])
def test_keys_names_and_files(pending):
    """BENCHMARK.json, and BENCHMARK.json with the pending cells the CPU
    tests add, which have no limits yet."""
    b = small.bench() if pending else bench()
    admitted = {w["name"] for w in bench()["workloads"]}
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert all(not w.startswith("/") and ".." not in w for w in b["command"])
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line_ok(c["source"])
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert (ROOT / c["file"]).is_file()
        data = json.loads((ROOT / c["file"]).read_text())
        assert (CHIP / "reference" / f"{data['reference']}.py").is_file()
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    cells = {}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and _line_ok(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        mix = json.loads((CHIP / "traffic" / f"{w['traffic']}.json").read_text())
        assert (CHIP / "drivers" / f"{mix['driver']}.py").is_file()
        assert (CHIP / "limits" / f"{w['name']}.json").is_file() == (
            w["name"] in admitted)
        cells[w["name"]] = w
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    used = {w["config"] for w in cells.values()}
    assert used == set(configs)

    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= set(cells)
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert "setup_s" in e2e and e2e["setup_s"] == set(cells)
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert _line_ok(m["layer"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= e2e[m["moves"]]
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
    for name in cells:
        reported = {k for k, v in e2e.items() if name in v}
        assert len(reported) >= 2
        assert any(name in m["workloads"] for m in b["per_layer"])
    names = ([m["name"] for m in b["end_to_end"] + b["per_layer"]]
             + list(cells) + list(configs))
    assert len(names) == len(set(names))


def test_run_seconds_fits_a_full_check():
    b = bench()
    rs = b["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def _first_cell() -> str:
    return bench()["workloads"][0]["name"]


def _run(args, cwd, **env):
    return subprocess.run([sys.executable, *args], cwd=cwd, capture_output=True,
                          text=True, timeout=300, env={**os.environ, **env})


def test_refuses_the_cpu():
    res = _run(["benchmarks/chip/run.py", "--workload", _first_cell(),
                "--seed", "1", "--seconds", "1"], ROOT, JAX_PLATFORMS="cpu")
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not res.stdout.strip()


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run(["benchmarks/chip/run.py", "--workload", _first_cell(),
                "--seed", "1", "--seconds", "1"], tmp_path)
    assert res.returncode != 0
    assert "No module named 'repro'" in res.stderr
    assert not res.stdout.strip()


@pytest.mark.parametrize("bad", [["--workload", "nope"], ["--seed", "x"]])
def test_rejects_bad_arguments(bad):
    args = {"--workload": _first_cell(), "--seed": "1", "--seconds": "1"}
    args.update(dict(zip(bad[::2], bad[1::2])))
    res = _run(["benchmarks/chip/run.py", *sum(args.items(), ())], ROOT,
               JAX_PLATFORMS="cpu")
    assert res.returncode != 0 and not res.stdout.strip()
