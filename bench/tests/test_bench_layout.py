"""The harness finds configurations, mixes and metrics by name, keeps to
the benchmark's rules, and refuses to run where it must."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from tiny import ROOT
from bench.harness import Layout, Result, read_metrics

LAYOUT = Layout()
SPEC = LAYOUT.spec
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _copy_layout(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))


def test_dropped_in_files_are_found_by_name(tmp_path):
    _copy_layout(tmp_path)
    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "msglog-omb-1kb.json").read_text())
    cfg.update(name="new-config", record_words=16)
    (bench / "configs" / "new-config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(
        {"loop": "closed", "enqueue_share": 0.25, "backlog_records": 64}))
    (bench / "metrics" / "new.metric.py").write_text(
        "def read(r):\n    return 42.0\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "new-config", "source": "x",
                            "file": "bench/configs/new-config.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new.cell", "config": "new-config",
                              "traffic": "new-mix", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new.metric", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "x", "moves": "ops_per_s",
                              "workloads": ["new.cell"]})
    spec["end_to_end"][0]["workloads"].append("new.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    lay = Layout(bench)
    cell = lay.cell("new.cell")
    assert cell.config["record_words"] == 16
    assert cell.mix["enqueue_share"] == 0.25
    assert [m["name"] for m in cell.per_layer] == ["new.metric"]
    got = read_metrics(lay, cell.per_layer, Result(None, {}, 0.0, {}))
    assert got == {"new.metric": {"value": 42.0, "unit": "ms"}}


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "msglog.saturate",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_run_refuses_without_the_program(tmp_path):
    _copy_layout(tmp_path)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert "{" not in proc.stdout


CELLS = {w["name"]: w for w in SPEC["workloads"]}
E2E = {m["name"]: m for m in SPEC["end_to_end"]}


def _reports(metric, cell):
    return cell in metric.get("workloads", CELLS)


def test_benchmark_json_keeps_its_rules():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in E2E.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    pairs = [(w["config"], w["traffic"]) for w in CELLS.values()]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in CELLS.values()) <= max(
        1, len(CELLS) // 2)
    for name in CELLS:
        e2e = [m for m in E2E.values() if _reports(m, name)]
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert any(_reports(m, name) for m in SPEC["per_layer"])
    for m in SPEC["per_layer"]:
        assert m["moves"] in E2E
        assert all(_reports(E2E[m["moves"]], c) for c in m["workloads"])
    for m in list(E2E.values()) + SPEC["per_layer"]:
        assert (LAYOUT.bench / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_files(cfg):
    data = LAYOUT.config(cfg["name"])
    assert cfg["file"].startswith("bench/configs/")
    assert data["name"] == cfg["name"] and data["reduced"] == cfg["reduced"]
    assert len(data["source"]) <= 200 and data["assumed"]
    assert data["guarantees"]
    assert (LAYOUT.bench / "reference" / f"{data['reference']}.py").is_file()


def test_no_file_names_another_configuration_mix_or_metric():
    named = ([c["name"] for c in SPEC["configs"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [m["name"] for k in ("end_to_end", "per_layer")
                for m in SPEC[k]])
    pats = {n: re.compile(r"(?<![\w.\-])" + re.escape(n) + r"(?![\w\-]|\.\w)")
            for n in named}
    bad = []
    for dirpath, dirs, files in os.walk(LAYOUT.bench):
        dirs[:] = [d for d in dirs if d not in ("tests", "__pycache__")]
        for f in files:
            own = f.rsplit(".", 1)[0]
            text = open(os.path.join(dirpath, f)).read()
            bad += [(f, n) for n, p in pats.items()
                    if n != own and p.search(text)]
    assert not bad, bad


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_reduced_names_every_setting_changed_from_the_source(cfg):
    """Each of the source's own settings the file restates is as the
    source has it, or is listed in ``reduced`` with its cause."""
    data = LAYOUT.config(cfg["name"])
    source = data.get("source_settings", {})
    changed = {k for k, v in source.items() if data.get(k) != v}
    assert changed <= set(data["reduced"]), changed
    assert set(data["reduced"]) == set(data.get("reduced_why", {}))
