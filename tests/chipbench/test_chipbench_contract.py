"""BENCHMARK.json keeps to the benchmark's contract, and the harness
finds every configuration, mix and metric by name, as files."""
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip import harness

ROOT = str(harness.ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return json.loads(raw)


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def test_top_level_and_command(bench):
    assert set(bench) == TOP
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    script = bench["command"][1]
    assert any(script.startswith(p + "/") for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_full_check_fits_its_time_with_24_cells(bench):
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_files_under_paths_are_named_from_name_characters(bench):
    for p in bench["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(ROOT, p)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert PATH.match(rel), rel


def test_configs(bench):
    used = {c["config"] for c in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "chip", "drivers", f"{cfg['driver']}.py"))


def test_workloads(bench):
    names, pairs = set(), set()
    four = 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["name"] not in names
        names.add(w["name"])
        assert NAME.match(w["traffic"]) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "chip", "traffic", f"{w['traffic']}.json"))
    assert 1 <= len(names) <= 24
    assert four <= max(1, math.floor(len(names) * 0.5))


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    seen = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        assert harness.reader_path(m["name"]).is_file(), m["name"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        for cell in m.get("workloads", []):
            mv = e2e[m["moves"]]
            assert "workloads" not in mv or cell in mv["workloads"]
    assert all(len(v) == 1 for v in layers.values())
    for cell in cells:
        reported = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.cell_metrics(bench, cell, True)


def test_a_new_mix_and_metric_are_found_as_files(tmp_path):
    """A later PR adds a traffic mix and a per-layer metric by adding
    files and entries only: the harness picks them up by name."""
    root = tmp_path / "co"
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    chip = root / "benchmarks" / "chip"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mix = json.loads((chip / "traffic" / "chat-steady.json").read_text())
    mix["rate_per_s"] = 1.25
    (chip / "traffic" / "chat-dummy.json").write_text(json.dumps(mix))
    (chip / "metrics" / "dummy_requests.chat.py").write_text(
        "def read(obs):\n    return float(len(obs['requests']))\n")
    bench["workloads"].append(
        {"name": "serve-dummy", "config": "qwen2-1.5b",
         "traffic": "chat-dummy", "chips": 1, "why": "test"})
    bench["end_to_end"][0].setdefault("workloads", []).append("serve-dummy")
    bench["per_layer"].append(
        {"name": "dummy_requests.chat", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "scheduler",
         "moves": bench["end_to_end"][0]["name"],
         "workloads": ["serve-dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    assert harness.load_mix("chat-dummy", chip)["rate_per_s"] == 1.25
    names = [m["name"] for m in harness.cell_metrics(bench, "serve-dummy",
                                                     True)]
    assert names == ["dummy_requests.chat"]
    got = harness.read_metrics(bench, "serve-dummy", True,
                               {"requests": [1, 2, 3]}, chip)
    assert got == {"dummy_requests.chat": {"value": 3.0, "unit": "count"}}


def test_a_metric_split_by_cell_group_reads_through_its_base_reader():
    assert harness.reader_path("step_mfu.chat").name == "step_mfu.py"
    assert harness.reader_path("step_mfu.code").name == "step_mfu.py"
    assert harness.reader_path("decode_step_ms.chat").name == \
        "decode_step_ms.chat.py"
    obs = {"trace": {"busy_s": 3.0, "window_s": 4.0}}
    assert harness.load_reader("device_idle_share.code").read(obs) == 25.0


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "serve-qwen2-1.5b-chat-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_the_measured_path_refuses_a_cpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_a_checkout_of_only_the_benchmark_refuses_to_run(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
