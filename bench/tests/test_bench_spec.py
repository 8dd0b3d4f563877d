"""BENCHMARK.json against the benchmark contract's naming rules, every
cell's files, and the harness's refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert (ROOT / p).is_dir()


def test_names_and_units_use_the_allowed_characters():
    names = [c["name"] for c in SPEC["configs"]] + CELLS
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["config"] for w in SPEC["workloads"]]
    names += [w["traffic"] for w in SPEC["workloads"]]
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len(set(CELLS)) == len(CELLS)


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for cell in CELLS:
        e2e = {m["name"] for m in SPEC["end_to_end"]
               if cell in m.get("workloads", [cell])}
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        layers = [m for m in SPEC["per_layer"]
                  if cell in m.get("workloads", [cell])]
        assert layers, cell


def test_every_moves_names_an_e2e_metric_of_each_listed_cell():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e, m
        target = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS, (m["name"], cell)
            assert cell in target.get("workloads", CELLS), (m["name"], cell)


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_has_its_files(cell):
    w = next(x for x in SPEC["workloads"] if x["name"] == cell)
    assert w["chips"] in (1, 4)
    assert (ROOT / "bench" / "configs" / f"{w['config']}.json").is_file()
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    limits = json.loads((ROOT / "bench" / "limits" / f"{cell}.json")
                        .read_text())["numbers"]
    assert limits and all(v["limit"] is not None for v in limits.values())
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        if cell in m.get("workloads", CELLS):
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_builds_its_scenario_at_tiny_width(cell):
    sys.path.insert(0, str(ROOT / "src"))
    from bench import drive
    w = next(x for x in SPEC["workloads"] if x["name"] == cell)
    config = json.loads((ROOT / "bench" / "configs"
                         / f"{w['config']}.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    config["scenario"]["width_mult"] = 0.0625
    d = drive.make(config, traffic, 2 ** 31 + 7)
    sc = d.scenario
    # the scenario carries the deployment; the run seed re-seeds the rest
    assert sc.seed == config["deployment_seed"] and d.seed == 2 ** 31 + 7
    assert sc.width_mult == 0.0625
    assert sc.net.n_devices == config["net"]["n_devices"]


def test_configs_list_what_they_cut():
    for c in SPEC["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["reduced"] == c["reduced"]
        assert c["source"] == f["source"]


def _run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_harness_refuses_a_cpu_and_prints_no_result():
    r = _run_bench(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_harness_refuses_a_kernel_override():
    r = _run_bench(ROOT, {"REPRO_FUSED_LINEAR_IMPL": "ref"})
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_harness_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_bench(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
