"""run.py end to end at a tiny size on the CPU (the size override that the
chip path refuses), for every cell, and the proof that a new cell is new files
and BENCHMARK.json entries only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def _run(root, *args, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(root, "benchmarks", "run.py"), *args], cwd=root, env=env,
                       capture_output=True, text=True, timeout=timeout)
    return p


def _last(p):
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_each_cell_runs_tiny_on_the_cpu(cell, trace):
    out = _last(_run(ROOT, "--workload", cell, "--seed", "3000000001", "--seconds", "5", "--trace", str(trace), "--tiny"))
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"  # a rehearsal says what it ran on
    section = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in BENCH[section] if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) <= listed and out["metrics"]
    if trace:
        assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(out["metrics"]) == listed and out["metrics"]["setup_s"]["value"] > 0


def test_without_the_override_no_chip_means_no_result():
    p = _run(ROOT, "--workload", BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "2", "--trace", "0")
    assert p.returncode != 0 and "{" not in (p.stdout.strip().splitlines() or [""])[-1]


def test_alone_in_a_directory_it_fails_with_no_result(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "benchmarks/run.py", "--workload", BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "2", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_new_cell_is_new_files_and_entries_only(tmp_path):
    """configs/x.json + traffic/y.json + layer_metrics/z.py + entries in
    BENCHMARK.json: run.py --workload x.y runs, and no existing file changed."""
    shutil.copytree(HERE, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("out", "__pycache__"))
    for part in ("ray_tpu", "src"):  # the program and the sources of its native libraries
        os.symlink(os.path.join(ROOT, part), tmp_path / part)
    b = tmp_path / "benchmarks"
    with open(b / "configs" / "gpt2-124m.json") as f:
        cfg = json.load(f)
    cfg.update(name="x", n_layer=3)
    cfg["tiny"]["n_layer"] = 3
    (b / "configs" / "x.json").write_text(json.dumps(cfg))
    with open(b / "traffic" / "pretrain-1k.json") as f:
        traffic = json.load(f)
    traffic.update(name="y", prefetch=1)
    (b / "traffic" / "y.json").write_text(json.dumps(traffic))
    (b / "layer_metrics" / "z.py").write_text("def read(view):\n    return float(view['counters']['steps'])\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "x", "source": cfg["source"], "file": "benchmarks/configs/x.json", "reduced": cfg["reduced"] + ["n_layer"], "why": "test"})
    bench["workloads"].append({"name": "x.y", "config": "x", "traffic": "y", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("x.y")
    bench["per_layer"].append({"name": "z.new", "unit": "steps", "better": "higher", "source": "program_counter", "layer": "Train loop",
                               "moves": "train_tokens_per_s_per_chip", "workloads": ["x.y"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _last(_run(str(tmp_path), "--workload", "x.y", "--seed", "5", "--seconds", "4", "--trace", "1", "--tiny"))
    assert out["correct"] is True and out["metrics"]["z.new"]["value"] == out["attempted"] > 0
