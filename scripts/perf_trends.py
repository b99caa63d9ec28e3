"""Perf-trend table + regression gate over the repo's benchmark artifacts.

The repo accumulates per-round benchmark JSONs (``BENCH_r*.json`` real-chip
training runs, ``PERF_r*.json`` control-plane microbench + scale envelope,
``SERVE_BENCH_r*.json`` serving runs) but until now nothing read them *as a
trajectory* — a perf regression was invisible unless someone diffed JSON by
hand.  This script parses every artifact into one run-indexed table, prints
it, and exits nonzero when a **tracked** metric's latest run regresses more
than ``--threshold`` (default 15%) against the best prior run.

Tracked vs informational series: the headline numbers (tok/s/chip, MFU,
queued-drain throughput, actor-creation rate, serve tokens/s + p99) gate
the build; the single-process microbench rows (`single client tasks sync`
etc.) are printed but NOT gated — their run-to-run variance on shared CI
boxes exceeds any useful threshold (r03→r04 swung −31% on an idle-loop
change of zero relevance), so gating them would only teach people to
ignore the gate.  Comparability guards keep the gate honest: BENCH/SERVE
rows only enter their series when the run executed on the TPU backend
(``platform == "tpu"``) and exited rc=0 — a run that died, or did not run
on the chip, is annotated in the table, not treated as a 100x regression.

Usage::

    python scripts/perf_trends.py                 # repo root, gate ON
    python scripts/perf_trends.py --dir DIR       # another artifact dir
    python scripts/perf_trends.py --out trends.txt  # also write the table
    python scripts/perf_trends.py --no-gate       # table only, exit 0

The repo root no longer holds a BENCH series (PERF_LEDGER.jsonl is the
record from this round on), so CI does not run this over the root; the
parsers are exercised on tests/fixtures/perf_trends.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

# series name -> (higher_is_better, tracked)
_SERIES_META: Dict[str, Tuple[bool, bool]] = {}


def _series(name: str, value: float, run: str, table: Dict[str, Dict[str, float]],
            higher_is_better: bool = True, tracked: bool = False):
    _SERIES_META[name] = (higher_is_better, tracked)
    table.setdefault(name, {})[run] = float(value)


def _run_label(path: str) -> Optional[str]:
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return f"r{int(m.group(1)):02d}" if m else None


def _load(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        print(f"perf_trends: skipping unreadable {path}: {e}", file=sys.stderr)
        return None


def _parse_bench(path: str, run: str, table, notes: List[str]):
    d = _load(path)
    if d is None:
        return
    parsed = d.get("parsed")
    if d.get("rc", 0) != 0 or not parsed:
        notes.append(f"{run}: BENCH run not comparable (rc={d.get('rc')}, "
                     f"no parsed metric) — excluded from gated series")
        return
    # step-dispatch pair (PR 13, train/jax/step_dag.py): driver overhead is
    # a host-path property measured on a CPU-pinned pair by design, so it
    # enters its series BEFORE the TPU-platform guard below — the guard
    # protects FLOP-bound numbers, not dispatch cost.  Gated automatically
    # once two runs carry it (find_regressions skips 1-point series).
    sd = parsed.get("step_dispatch") or {}
    if isinstance(sd.get("dag_step_ms"), (int, float)):
        _series("bench.train_dispatch_dag_step_ms", sd["dag_step_ms"], run,
                table, higher_is_better=False, tracked=True)
    if isinstance(sd.get("eager_step_ms"), (int, float)):
        _series("bench.train_dispatch_eager_step_ms", sd["eager_step_ms"],
                run, table, higher_is_better=False)
    if isinstance(sd.get("dispatch_speedup"), (int, float)):
        _series("bench.train_dispatch_speedup", sd["dispatch_speedup"], run,
                table, tracked=True)
    if parsed.get("platform") != "tpu":
        notes.append(f"{run}: BENCH ran on {parsed.get('platform')!r} "
                     "(backend fallback) — excluded from gated series")
        return
    _series("bench.gpt2_tok_per_s_per_chip", parsed.get("value", 0.0), run,
            table, tracked=True)
    if parsed.get("mfu") is not None:
        _series("bench.gpt2_mfu", parsed["mfu"], run, table, tracked=True)
    if parsed.get("step_ms") is not None:
        _series("bench.gpt2_step_ms", parsed["step_ms"], run, table,
                higher_is_better=False)


def _parse_perf(path: str, run: str, table, notes: List[str]):
    d = _load(path)
    if d is None:
        return
    if d.get("rc", 0) != 0:
        notes.append(f"{run}: PERF run not comparable (rc={d.get('rc')}) — "
                     "excluded")
        return
    # two historical shapes: flat microbench (r03) vs
    # {"microbench": ..., "scale_envelope": ...} (r04+)
    micro = d.get("microbench")
    if micro is None and "scale_envelope" not in d:
        micro = {k: v for k, v in d.items() if isinstance(v, (int, float))}
    for k, v in (micro or {}).items():
        if isinstance(v, (int, float)):
            _series(f"perf.micro.{k}", v, run, table)  # informational only
    # device-tier transfer pair (PR 17, core/DEVICE_TIER.md): the MB/s
    # rows are box-sensitive so they stay informational above, but the
    # device-vs-host RATIOS are same-box same-run quotients — variance
    # cancels, so a ratio collapse means the device plane itself broke
    # (e.g. pulls silently falling back to host TCP).  Gate those.
    for key, series in (
        ("obs transfer device vs host speedup", "perf.obs_transfer_device_speedup"),
        ("broadcast tree vs host speedup", "perf.broadcast_tree_speedup"),
    ):
        v = (micro or {}).get(key)
        if isinstance(v, (int, float)):
            _series(series, v, run, table, tracked=True)
    se = d.get("scale_envelope") or {}
    qt = se.get("queued_tasks_10k") or {}
    if "throughput_per_sec" in qt:
        _series("perf.queued_drain_per_sec", qt["throughput_per_sec"], run,
                table, tracked=True)
    mt = se.get("many_tasks_10k") or {}
    if "tasks_per_sec" in mt:
        _series("perf.many_tasks_per_sec", mt["tasks_per_sec"], run, table,
                tracked=True)
    ma = se.get("many_actors") or {}
    if "actors_per_sec" in ma:
        _series("perf.actor_create_per_sec", ma["actors_per_sec"], run,
                table, tracked=True)
    bc = se.get("broadcast_100mb_4nodes") or {}
    if "aggregate_mb_per_sec" in bc:
        _series("perf.broadcast_mb_per_sec", bc["aggregate_mb_per_sec"], run,
                table)


def _parse_serve(path: str, run: str, table, notes: List[str]):
    d = _load(path)
    if d is None:
        return
    if d.get("rc", 0) != 0:
        notes.append(f"{run}: SERVE_BENCH run not comparable "
                     f"(rc={d.get('rc')}) — excluded from gated series")
        return
    # fleet survival rows (serve/FLEET.md): scaling reaction, failover
    # count and TTFT-under-kill are CONTROL-plane properties measured on
    # a tiny CPU model by design, so — like the step-dispatch pair — they
    # enter their series BEFORE the TPU-platform guard below.  Gated
    # automatically once two runs carry them.
    fleet = d.get("fleet") or {}
    if isinstance(fleet.get("scale_out_reaction_s"), (int, float)):
        _series("serve.fleet_scale_out_reaction_s",
                fleet["scale_out_reaction_s"], run, table,
                higher_is_better=False, tracked=True)
    if isinstance(fleet.get("ttft_ms_p99_no_kill"), (int, float)):
        _series("serve.fleet_ttft_ms_p99_no_kill",
                fleet["ttft_ms_p99_no_kill"], run, table,
                higher_is_better=False, tracked=True)
    if isinstance(fleet.get("ttft_ms_p99_with_kill"), (int, float)):
        _series("serve.fleet_ttft_ms_p99_with_kill",
                fleet["ttft_ms_p99_with_kill"], run, table,
                higher_is_better=False, tracked=True)
    if isinstance(fleet.get("failovers"), (int, float)):
        _series("serve.fleet_failovers_per_kill", fleet["failovers"], run,
                table)  # informational: count, not a perf axis
    if d.get("platform") != "tpu":
        notes.append(f"{run}: SERVE_BENCH ran on {d.get('platform')!r} — "
                     "excluded from gated series")
        return
    if isinstance(d.get("value"), (int, float)):
        _series("serve.decode_tok_per_s_per_chip", d["value"], run, table,
                tracked=True)
    loads = d.get("loads") or []
    if loads:
        peak = max(loads, key=lambda l: l.get("offered_concurrency", 0))
        if "p99_ms" in peak:
            _series("serve.p99_ms_at_peak_load", peak["p99_ms"], run, table,
                    higher_is_better=False, tracked=True)
        if "tokens_per_sec" in peak:
            _series("serve.tokens_per_sec_at_peak_load",
                    peak["tokens_per_sec"], run, table, tracked=True)


def build_table(artifact_dir: str):
    """Parse every benchmark artifact under ``artifact_dir`` into
    {series: {run: value}} plus comparability notes."""
    _SERIES_META.clear()
    table: Dict[str, Dict[str, float]] = {}
    notes: List[str] = []
    parsers = (
        ("BENCH_r*.json", _parse_bench),
        ("PERF_r*.json", _parse_perf),
        ("SERVE_BENCH_r*.json", _parse_serve),
    )
    for pattern, parse in parsers:
        for path in sorted(glob.glob(os.path.join(artifact_dir, pattern))):
            run = _run_label(path)
            if run:
                parse(path, run, table, notes)
    return table, notes


def find_regressions(table, threshold: float) -> List[str]:
    """Tracked series whose LATEST run regresses >threshold vs the best
    prior run.  Series with fewer than two points pass trivially."""
    out = []
    for name, by_run in sorted(table.items()):
        higher_better, tracked = _SERIES_META.get(name, (True, False))
        if not tracked or len(by_run) < 2:
            continue
        runs = sorted(by_run)
        last_run, last = runs[-1], by_run[runs[-1]]
        prior = [by_run[r] for r in runs[:-1]]
        best = max(prior) if higher_better else min(prior)
        if best == 0:
            continue
        if higher_better:
            drop = 1.0 - last / best
        else:
            drop = last / best - 1.0
        if drop > threshold:
            direction = "down" if higher_better else "up"
            out.append(
                f"{name}: {last_run}={last:g} is {drop:.1%} {direction} vs "
                f"best prior {best:g} (threshold {threshold:.0%})"
            )
    return out


def render(table, notes) -> str:
    runs = sorted({r for by_run in table.values() for r in by_run})
    name_w = max((len(n) for n in table), default=10) + 2
    lines = []
    hdr = f"{'series':{name_w}s} " + " ".join(f"{r:>10s}" for r in runs) + "   gate"
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for name in sorted(table):
        higher_better, tracked = _SERIES_META.get(name, (True, False))
        cells = " ".join(
            f"{table[name][r]:10.4g}" if r in table[name] else f"{'-':>10s}"
            for r in runs
        )
        tag = "tracked" + ("" if higher_better else " (lower=better)") if tracked else "info"
        lines.append(f"{name:{name_w}s} {cells}   {tag}")
    if notes:
        lines.append("")
        lines.append("comparability notes:")
        lines.extend(f"  - {n}" for n in notes)
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="perf_trends")
    parser.add_argument(
        "--dir",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="artifact directory (default: repo root)",
    )
    parser.add_argument("--threshold", type=float, default=0.15,
                        help="regression gate as a fraction (default 0.15)")
    parser.add_argument("--out", default=None, help="also write the table here")
    parser.add_argument("--no-gate", action="store_true",
                        help="print the table, skip the regression gate")
    args = parser.parse_args(argv)

    table, notes = build_table(args.dir)
    if not table:
        print(f"perf_trends: no benchmark artifacts under {args.dir}",
              file=sys.stderr)
        return 2
    text = render(table, notes)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.no_gate:
        return 0
    regressions = find_regressions(table, args.threshold)
    if regressions:
        print("\nREGRESSIONS:", file=sys.stderr)
        for r in regressions:
            print(f"  FAIL {r}", file=sys.stderr)
        return 1
    tracked = sum(1 for m in _SERIES_META.values() if m[1])
    print(f"\nperf_trends: OK ({tracked} tracked series, no regression "
          f">{args.threshold:.0%} vs best prior run)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
