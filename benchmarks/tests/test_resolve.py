"""BENCHMARK.json against the files it names, and the rule that no harness
code branches on the name of a cell, a configuration or a metric."""

import glob
import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def test_every_name_resolves_to_a_file():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert os.path.isfile(os.path.join(HERE, "drivers", f"{data['kind']}.py"))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        with open(os.path.join(HERE, "traffic", f"{w['traffic']}.json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(HERE, "loadgen", f"{traffic['kind']}.py"))
        assert len(w["why"]) <= 200
        listed = [m for m in BENCH["end_to_end"] if "workloads" not in m or w["name"] in m["workloads"]]
        assert {"setup_s"} < {m["name"] for m in listed}
    for m in BENCH["per_layer"]:
        base = m["name"].partition(".")[0]
        reader = importlib.import_module(f"benchmarks.layer_metrics.{base}")
        assert callable(reader.read)
        assert m["moves"] in e2e
        for cell in m.get("workloads", [w["name"] for w in BENCH["workloads"]]):
            reported = {e["name"] for e in BENCH["end_to_end"] if "workloads" not in e or cell in e["workloads"]}
            assert m["moves"] in reported, (m["name"], cell)


def test_contract_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[section]]
        assert len(names) == len(set(names)) and all(name.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_no_harness_code_names_a_cell_a_configuration_or_a_metric():
    names = {x["name"] for s in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[s]}
    names |= {w["traffic"] for w in BENCH["workloads"]}
    names -= {"setup_s"}  # every cell has it; run.py computes it itself
    # end-to-end metrics are the yardstick: the driver that measures one says its name
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    offenders = []
    for path in glob.glob(os.path.join(HERE, "**", "*.py"), recursive=True):
        rel = os.path.relpath(path, HERE)
        if rel.startswith("tests" + os.sep):
            continue
        with open(path) as f:
            src = f.read()
        code = re.sub(r'"""[\s\S]*?"""', "", src)  # docstrings may explain by example
        code = re.sub(r"#.*", "", code)
        for n in names:
            if re.search(r"""["']""" + re.escape(n) + r"""["']""", code):
                if n in e2e and (rel.startswith("drivers" + os.sep) or rel == "sweep.py"):
                    continue
                offenders.append((rel, n))
    assert not offenders, offenders
