"""BENCHMARK.json resolves to files found by name; names and units keep
to their character sets; a new cell is files plus an entry; a run without
an accelerator listed in bench/peaks.json exits nonzero with no result."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_resolves_to_its_files(cell):
    info = harness.resolve(BENCH, cell)
    assert os.path.isfile(info["driver"])
    assert info["config"]["reduced"] == info["config_entry"]["reduced"]
    assert os.path.isfile(info["limits"])
    names = {m["name"] for m in info["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert info["per_layer"], "every cell reports a per-layer metric"
    for path in info["metric_files"].values():
        assert os.path.isfile(path)
        assert callable(harness.load_module(path).read)


def test_names_units_and_keys_keep_to_the_contract():
    top = {"command", "paths", "run_seconds", "configs", "workloads",
           "end_to_end", "per_layer"}
    assert set(BENCH) == top
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert e2e == {"setup_s", "voxels_per_s", "itl_p95_ms", "tokens_per_s"}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


def test_a_new_cell_is_files_and_an_entry(tmp_path):
    """A mix, a configuration, a metric and a cell added as new files plus
    BENCHMARK.json entries resolve without an edit to any file."""
    root = tmp_path / "repo"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    b = root / "bench"
    (b / "traffic" / "chat-long.json").write_text(json.dumps(dict(
        harness.read_json(os.path.join(harness.BENCH, "traffic",
                                       "chat.json")), warmup_s=20)))
    (b / "configs" / "qwen2-1.5b-n8.json").write_text(json.dumps(dict(
        harness.read_json(os.path.join(harness.BENCH, "configs",
                                       "qwen2-1.5b.json")), mask_samples=8)))
    (b / "limits" / "qwen2-1.5b-n8.chat-long.json").write_text("{}")
    (b / "metrics" / "lm.new_metric.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    bench["configs"].append({"name": "qwen2-1.5b-n8", "source": "x",
                             "file": "bench/configs/qwen2-1.5b-n8.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "qwen2-1.5b-n8.chat-long",
                               "config": "qwen2-1.5b-n8",
                               "traffic": "chat-long", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "lm.new_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "x", "moves": "itl_p95_ms"})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("qwen2-1.5b-n8.chat-long")
    info = harness.resolve(bench, "qwen2-1.5b-n8.chat-long", root=str(root))
    assert info["config"]["mask_samples"] == 8
    assert info["traffic"]["warmup_s"] == 20
    assert os.path.isfile(info["limits"])
    assert "lm.new_metric" in info["metric_files"]
    assert info["metric_files"]["lm.new_metric"].startswith(str(b))


def test_an_unknown_device_is_an_error():
    with pytest.raises(harness.NoDevice):
        harness.peaks("TPU v0 imaginary")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoDevice):
        harness.devices(1)               # the CPU is not an accelerator


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ivim-clinical.scan",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_a_run_on_the_cpu_exits_nonzero_and_reports_nothing():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not an accelerator" in p.stderr


def test_a_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
