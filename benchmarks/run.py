"""Run one cell of BENCHMARK.json once, in a new process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic mix and layer metrics BY NAME:
``configs/<config>.json``, ``traffic/<traffic>.json``, the driver
``drivers/<config kind>.py``, the generator ``loadgen/<traffic kind>.py`` and
one reader ``layer_metrics/<metric name before the dot>.py`` per layer metric.
Adding a cell, a configuration, a mix or a layer metric is adding files and
BENCHMARK.json entries; nothing here names any of them.

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, in a
traced run, ``breakdown``.  Exit code 2 and no result where the host lacks
the chips, or where the program (``ray_tpu``) is not beside ``benchmarks/``.

``correct: false`` and a non-zero exit are statements about the program under
test (benchmarks/README.md, "What makes a run incorrect, and what never
does").  So a run first waits until no other process holds a chip
(``cluster.wait_for_chips``), and a failure BEFORE its measured window opens is
tried once more in a fresh cluster (``may_retry``); the first attempt's
traceback and worker-log tails are kept in ``out/<run>/attempt1.err`` and the
result line says ``"attempts": 2``.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys
import time
import traceback
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def process_start_epoch() -> float:
    """When this process began, from /proc (the interpreter's own start-up
    belongs to set-up); falls back to now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


@dataclasses.dataclass
class Context:
    """What a driver is given."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    tiny: bool
    out_dir: str
    trace_dir: str

    @property
    def window_mark(self) -> str:
        """A file that exists once the measured window has opened (a training
        window opens inside the worker, which is given this path)."""
        return os.path.join(self.out_dir, "window_opened") if self.out_dir else ""

    def window_opens(self) -> None:
        """Called by a driver as the last thing before its window (pre-roll
        included): from here on a failure is the run's, and is never retried."""
        if self.window_mark:
            open(self.window_mark, "w").close()


def may_retry(attempt: int, window_opened: bool) -> bool:
    """Whether a run that RAISED may be tried again in a fresh cluster: once,
    and only if its measured window had not opened (cluster start, worker
    spawn, ``jax.devices()``, the reference-check actor, compile, warm-up).
    A run that came back with a result, ``correct`` or not, never gets here."""
    return attempt == 1 and not window_opened


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def merge_tiny(data: Dict[str, Any]) -> Dict[str, Any]:
    """Apply a file's ``tiny`` overrides (one level of nesting): the CPU
    rehearsal's sizes.  The chip path never calls this."""
    out = dict(data)
    for k, v in data.get("tiny", {}).items():
        out[k] = {**out[k], **v} if isinstance(v, dict) and isinstance(out.get(k), dict) and "dist" not in v else v
    return out


def load_cell(workload: str, tiny: bool):
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic mix),
    found by name; the files' ``tiny`` overrides applied for the rehearsal."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r} in BENCHMARK.json (have: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    if tiny:
        config, traffic = merge_tiny(config), merge_tiny(traffic)
    return bench, cell, config, traffic


def measure(ctx: Context, driver, cluster, run_id: str):
    """``driver.run(ctx)`` in a cluster of its own -> (what it returned,
    attempts, the first attempt's error in one line or None, what each wait
    for the chips found), or None where it raised: the traceback and the
    worker-log tails are then on standard error.  Before each attempt: wait
    until no other process holds a chip (inside ``setup_s``: it is set-up).
    A first attempt that raised before its window opened (``may_retry``)
    leaves all it said in ``<out_dir>/attempt1.err`` and is tried once more."""
    chips = int(ctx.cell["chips"])
    attempt, first_error, waits = 0, None, []
    while True:
        attempt += 1
        if os.path.exists(ctx.window_mark):
            os.remove(ctx.window_mark)
        if not ctx.tiny:
            waits.append(cluster.wait_for_chips())
        session = ""
        try:
            cluster.start(chips, ctx.tiny)
            session = cluster.session_dir()
            raw = driver.run(ctx)
            cluster.assert_driver_off_jax()
            return raw, attempt, first_error, waits
        except BaseException as e:  # noqa: BLE001 -- any failure: say why, print no result, exit non-zero
            said = traceback.format_exc() + cluster.log_tails(session)
            print(said, file=sys.stderr, end="", flush=True)
            if isinstance(e, (KeyboardInterrupt, SystemExit)) or not may_retry(attempt, os.path.exists(ctx.window_mark)):
                return None
            first_error = (f"{type(e).__name__}: {e}".splitlines() or [type(e).__name__])[0][:300]
            with open(os.path.join(ctx.out_dir, "attempt1.err"), "w") as f:
                f.write(f"attempt 1 of {run_id} failed before its window opened; the wait for the chips found: {json.dumps(waits[-1:])}\n{said}")
            print(f"run.py: attempt 1 failed before the window opened ({first_error}); trying once more in a fresh cluster", file=sys.stderr, flush=True)
        finally:
            cluster.stop()


def metrics_of(bench: Dict[str, Any], section: str, cell: str):
    return [m for m in bench[section] if "workloads" not in m or cell in m["workloads"]]


def layer_metric_values(bench, cell_name: str, view: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric the cell lists, from its own reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_of(bench, "per_layer", cell_name):
        base, _, suffix = m["name"].partition(".")
        reader = importlib.import_module(f"benchmarks.layer_metrics.{base}")
        value = reader.read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    started = process_start_epoch()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true", help=argparse.SUPPRESS)  # CPU rehearsal; refused where a chip is
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    try:
        bench, cell, config, traffic = load_cell(args.workload, args.tiny)
    except KeyError as e:
        print(f"run.py: {e.args[0]}", file=sys.stderr)
        return 2
    if int(config["chips"]) != int(cell["chips"]):
        print(f"run.py: cell asks for {cell['chips']} chips, its configuration is laid out for {config['chips']}", file=sys.stderr)
        return 2
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])

    try:
        import ray_tpu  # noqa: F401 -- the system under test lies beside benchmarks/
    except ImportError as e:
        print(f"run.py: {e}: the program is not beside benchmarks/; nothing was run", file=sys.stderr)
        return 2
    from benchmarks.drivers import cluster

    try:
        cache_dir = cluster.prepare_env(ROOT, int(cell["chips"]), args.tiny)
    except cluster.NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 2

    run_id = f"{args.workload}-s{args.seed}-t{args.trace}"
    out_dir = os.path.join(HERE, "out", run_id)
    trace_dir = os.path.join(out_dir, "trace")
    if os.path.isdir(trace_dir):
        import shutil

        shutil.rmtree(trace_dir)
    os.makedirs(out_dir, exist_ok=True)
    if os.path.exists(os.path.join(out_dir, "attempt1.err")):
        os.remove(os.path.join(out_dir, "attempt1.err"))  # an earlier run's of the same name
    ctx = Context(cell, config, traffic, args.seed, seconds, bool(args.trace), args.tiny, out_dir, trace_dir)
    driver = importlib.import_module(f"benchmarks.drivers.{config['kind']}")

    measured = measure(ctx, driver, cluster, run_id)
    if measured is None:
        return 1
    raw, attempt, first_error, waits = measured

    if not args.tiny and raw["device"]["platform"] != "tpu":
        print(f"run.py: the cell ran on platform {raw['device']['platform']!r}, not tpu", file=sys.stderr)
        return 1

    setup_s = raw["window_epoch"] - started
    e2e = dict(raw["e2e"])
    e2e["setup_s"] = (setup_s, "s")
    device = dict(raw["device"])
    result: Dict[str, Any] = {"correct": bool(raw["correct"]), "attempted": raw["attempted"], "failed": raw["failed"],
                              "attempts": attempt, "chips_wait_s": sum(w["chips_wait_s"] for w in waits)}
    if first_error:
        result["first_error"] = first_error
    raw["notes"].update(attempts=attempt, first_error=first_error, chips_wait_s=result["chips_wait_s"], chip_holders=[w["chip_holders"] for w in waits])
    detail = {"run": run_id, "seconds": seconds, "cache_dir": cache_dir, "problems": raw["problems"], "notes": raw["notes"],
              "e2e_all": {k: v[0] for k, v in e2e.items()}, "counters": {k: v for k, v in raw["counters"].items() if not isinstance(v, list)}}

    if args.trace:
        from benchmarks import stats, trace_reduce

        path = trace_reduce.find_xplane(trace_dir)
        planes = trace_reduce.load_xplane(path) if path else []
        reduced = trace_reduce.reduce_trace(planes, host_thread=raw.get("host_thread"), name_by=raw.get("gap_names"), cpu_rehearsal=args.tiny) if path else None
        if reduced is None:
            print(f"run.py: the traced run left no device trace under {trace_dir}: no result", file=sys.stderr)
            return 1
        view = {
            "trace": reduced, "planes": planes, "counters": raw["counters"], "config": config, "traffic": traffic,
            "e2e": {k: v[0] for k, v in e2e.items()}, "records": raw.get("records", []),
            "peaks": stats.load_peaks(device["kind"]) if not args.tiny else {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0, "hbm_bytes": 1.0},
        }
        result["metrics"] = layer_metric_values(bench, args.workload, view)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
        detail["trace"] = {k: reduced[k] for k in ("devices", "window_s", "busy_s", "busy_by_device_s", "gap_count", "module_s", "module_count")}
        detail["trace"]["top_ops"] = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:40]
        detail["trace"]["xplane"] = path
    else:
        listed = {m["name"]: m for m in metrics_of(bench, "end_to_end", args.workload)}
        missing = [n for n in listed if n not in e2e]
        if missing:
            print(f"run.py: the driver gave no value for {missing}", file=sys.stderr)
            return 1
        result["metrics"] = {n: {"value": e2e[n][0], "unit": listed[n]["unit"]} for n in listed}
    result["device"] = device

    with open(os.path.join(out_dir, "detail.json"), "w") as f:
        json.dump(detail, f, indent=1, default=str)
    print("detail:", json.dumps(detail, default=str), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
