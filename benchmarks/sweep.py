"""Find the knee of an open-loop cell once: one process, one set-up, a few
fixed rates of ``--seconds`` each.  The knee is the highest rate whose
backlog does not grow; the cell's traffic file then gets 0.8 of it.

    python3 benchmarks/sweep.py --workload <cell> --rates 1.5,2,2.5,3 --seconds 30

Prints one JSON line per rate and writes them to benchmarks/out/sweep-<cell>.jsonl.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def in_flight_at(records, t: float) -> int:
    return sum(1 for r in records if r["sent"] is not None and r["sent"] <= t and (r["done"] is None or r["done"] > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tiny", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run
    from benchmarks import stats
    from benchmarks.drivers import cluster, serve as serve_driver

    _, cell, config, traffic = bench_run.load_cell(args.workload, args.tiny)
    try:
        cluster.prepare_env(ROOT, int(cell["chips"]), args.tiny)
    except cluster.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 2
    import ray_tpu
    from ray_tpu import serve

    loadgen = importlib.import_module(f"benchmarks.loadgen.{traffic['kind']}")
    os.makedirs(os.path.join(HERE, "out"), exist_ok=True)
    out_path = os.path.join(HERE, "out", f"sweep-{args.workload}.jsonl")
    try:
        cluster.start(int(cell["chips"]), args.tiny)
        dep, client, info, *_ = serve_driver.deploy_and_warm(config, args.seed, {})
        with open(out_path, "w") as out:
            for rate in [float(x) for x in args.rates.split(",")]:
                # let the last rate's backlog drain first
                while client.method("engine_stats")["slots_active"] > 0:
                    time.sleep(0.5)
                s0 = client.method("engine_stats")
                res = loadgen.run(client, {**traffic, "rate_rps": rate}, args.seed, args.seconds, config["vocab_size"])
                s1 = client.method("engine_stats")
                recs = [r for r in res["records"] if 0 <= r["due"] < args.seconds]
                ok = [r for r in recs if r["frames"] and not r["error"]]
                thirds = [[(r["frames"][0][0] - r["due"]) * 1e3 for r in ok if k * args.seconds / 3 <= r["due"] < (k + 1) * args.seconds / 3] for k in range(3)]
                ttft = [x for t in thirds for x in t]
                gaps = [g for r in ok for g in stats.token_gaps_ms(r["frames"])]
                row = {
                    "rate_rps": rate, "platform": info["platform"], "requests": len(recs),
                    "failed": sum(1 for r in recs if r["error"] or r["done"] is None or r["tokens"] != r["budget"]),
                    "ttft_p50_ms_by_third": [stats.percentile(t, 50) if t else None for t in thirds],
                    "ttft_p50_ms": stats.percentile(ttft, 50) if ttft else None,
                    "ttft_p90_ms": stats.percentile(ttft, 90) if ttft else None,
                    "token_gap_p50_ms": stats.percentile(gaps, 50) if gaps else None,
                    "token_gap_p95_ms": stats.percentile(gaps, 95) if gaps else None,
                    "in_flight_at_thirds": [in_flight_at(res["records"], args.seconds * k / 3) for k in (1, 2, 3)],
                    "iter_ms": 1e3 * (args.seconds + float(traffic.get("preroll_s", 0))) / max(1.0, s1["iterations"] - s0["iterations"]),
                    "tokens_per_s": sum(r["tokens"] for r in ok) / args.seconds,
                }
                print(json.dumps(row), flush=True)
                out.write(json.dumps(row) + "\n")
        serve.delete(dep.name)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
