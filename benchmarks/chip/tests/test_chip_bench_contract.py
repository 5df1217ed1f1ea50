"""The benchmark's files against its own contract, on the CPU.

Every cell of ``BENCHMARK.json`` resolves its configuration, traffic,
driver, reference and metric files by name; names and units keep to
the allowed characters; the peaks table refuses a device it does not
know; and the command fails, printing no result, without a TPU or in a
checkout that holds only the benchmark.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
sys.path.insert(0, str(CHIP))

import bench  # noqa: E402
import reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in SPEC["workloads"]]
PER_LAYER = [m["name"] for m in SPEC["per_layer"]]


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmarks/chip/bench.py"]
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_units_and_lines():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        names += [c["name"], *c["reduced"]]
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        names += [w["name"], w["config"], w["traffic"]]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in SPEC["per_layer"]:
        assert _line(m["layer"])
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads"):
        got = [x["name"] for x in SPEC[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_bounds_and_run_seconds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25, m
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with all 24 cells fits the driver's budget
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c, cfg_entry = bench.cell_spec(SPEC, cell)
    cfg_file = ROOT / cfg_entry["file"]
    assert cfg_file.is_file() and CHIP in cfg_file.parents
    config = bench.load_json(cfg_file)
    assert (CHIP / "configs" / f"{config['reference']}.py").is_file()
    traffic = bench.load_json(CHIP / "traffic" / f"{c['traffic']}.json")
    driver = bench.load_module(CHIP / "drivers" / f"{traffic['driver']}.py",
                               f"driver_{traffic['driver']}")
    for fn in ("setup", "window", "check"):
        assert callable(getattr(driver, fn))
    # every number the driver compares has its limit in the config
    assert set(driver.COMPARED) <= set(config["limits"])
    # the cell reports setup_s, another end-to-end metric and a layer
    e2e = [m["name"] for m in bench.cell_metrics(SPEC, c, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert bench.cell_metrics(SPEC, c, "per_layer")


@pytest.mark.parametrize("metric", PER_LAYER)
def test_metric_reader_resolves_by_name(metric):
    m = {x["name"]: x for x in SPEC["per_layer"]}[metric]
    mod = bench.load_module(CHIP / "metrics" / f"{metric}.py", f"m_{metric}")
    assert callable(mod.read)
    e2e = {x["name"]: x for x in SPEC["end_to_end"]}
    assert m["moves"] in e2e
    for cell in m["workloads"]:
        assert cell in CELLS
        if "workloads" in e2e[m["moves"]]:
            assert cell in e2e[m["moves"]]["workloads"]


def test_peaks_refuse_an_unknown_device_kind():
    assert reduce.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert reduce.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        reduce.load_peaks("TPU v99 imaginary")


def _run_command(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload", CELLS[0],
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_a_tpu():
    proc = _run_command(ROOT)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
    assert "TPU" in proc.stderr


def test_command_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_command(tmp_path)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
