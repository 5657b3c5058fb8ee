"""Cells are found by name from files of their own; an unknown device and
a missing TPU both stop a run before it prints a result."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks_for("TPU v9 imaginary")
    assert spec.peaks_for("TPU v5 lite")["int8_ops"] == 393e12


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    cell = BENCH["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_every_cell_resolves_to_its_files():
    for w in BENCH["workloads"]:
        cell = spec.find_cell(w["name"])
        assert cell.mix["name"] == w["traffic"]
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m.moves in names, (w["name"], m.name)
            assert callable(spec.metric_reader(m.name))


def test_names_and_keys_keep_to_the_contract():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert (ROOT / BENCH["command"][1]).is_file()


def test_a_cell_of_new_files_needs_no_edit(tmp_path):
    """A later PR adds a configuration, a mix and a metric as new files and
    one ``workloads`` entry; every file that was there stays as it was."""
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench")
    before = {
        p.relative_to(tmp_path): p.read_bytes()
        for p in (tmp_path / "perfbench").rglob("*") if p.is_file()
    }
    config = json.loads((ROOT / BENCH["configs"][0]["file"]).read_text())
    config["sizes"]["num_hidden_layers"] = 28
    (tmp_path / "perfbench/configs/new_model.json").write_text(json.dumps(config))
    mix = json.loads((ROOT / "perfbench/mixes/gen.json").read_text())
    mix["output"]["lognormal_median"] = 512
    (tmp_path / "perfbench/mixes/longer.json").write_text(json.dumps(mix))
    (tmp_path / "perfbench/metrics/steps_read.tps.py").write_text(
        "def read(ctx):\n    return float(len(ctx.steps)) or None\n"
    )
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "new_model", "source": "https://example.org/config.json",
         "file": "perfbench/configs/new_model.json", "reduced": [], "why": "new"}
    )
    bench["workloads"].append(
        {"name": "new_model.longer", "config": "new_model", "traffic": "longer",
         "chips": 1, "why": "new"}
    )
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("new_model.longer")
    bench["per_layer"].append(
        {"name": "steps_read.tps", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "scheduler", "moves": "tokens_per_s",
         "workloads": ["new_model.longer"]}
    )
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.find_cell("new_model.longer", tmp_path)
    assert cell.config["sizes"]["num_hidden_layers"] == 28
    assert cell.mix["output"]["lognormal_median"] == 512
    assert [m.name for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
    assert [m.name for m in cell.per_layer] == ["steps_read.tps"]
    reader = spec.metric_reader("steps_read.tps", tmp_path / "perfbench")
    assert reader(type("Ctx", (), {"steps": [1, 2]})) == 2.0
    for path, data in before.items():
        assert (tmp_path / path).read_bytes() == data
