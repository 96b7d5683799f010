"""BENCHMARK.json names files that exist, the harness finds a cell's files
by name alone, and ``run.py`` refuses to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import smoke_root

BENCH = smoke_root.BENCH
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_bounds(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])


def test_every_name_resolves_to_its_file(bench):
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        fam = harness.family_module(cfg["family"])
        assert hasattr(fam, "make_params") and hasattr(fam, "program_bundle")
        harness.reference_module(cfg["family"])
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"])
        if cell.traffic["kind"] == "serve":
            assert hasattr(cell.family(), "token_flops")
            assert hasattr(cell.reference(), "served_gaps")
        else:
            assert cell.traffic["kind"] == "train"
            assert hasattr(cell.family(), "train_flops_per_token")
            assert hasattr(cell.reference(), "train_readings")
        assert cell.end_to_end and cell.per_layer
    for m in bench["per_layer"]:
        assert callable(harness.layer_reader(m["name"]).read)


def test_per_layer_metrics_move_a_metric_their_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells)
    for cell in cells:
        reported = [m for m in bench["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2          # setup_s and one more


def test_added_files_make_a_new_cell_and_metric(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, with new entries only, load without editing any file."""
    root = smoke_root.make(str(tmp_path))
    with open(os.path.join(root, "bench", "layers", "throwaway.smoke.py"),
              "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "throwaway.smoke", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "serve_tokens_per_s",
        "workloads": ["qwen3-smoke.smoke-docqa"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = harness.Cell("qwen3-smoke.smoke-docqa", root)
    assert cell.config["program_preset"] == "smoke"
    assert cell.traffic["loop"] == "closed"
    names = [m["name"] for m in cell.per_layer]
    assert "throwaway.smoke" in names
    assert harness.layer_reader("throwaway.smoke", root).read({}) == 42.0
    # every file of the repo's benchmark is unchanged in the copy
    for d, _, files in os.walk(BENCH):
        if "__pycache__" in d or os.sep + "tests" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), BENCH)
            with open(os.path.join(d, f), "rb") as a, \
                    open(os.path.join(root, "bench", rel), "rb") as b:
                assert a.read() == b.read(), rel


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-4b.chat",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
