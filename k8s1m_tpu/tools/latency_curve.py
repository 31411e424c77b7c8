"""Latency/throughput curve: p50/p95/p99 schedule-to-bind vs offered load.

The reference's primary metric is two-headed — binds/s AND p50
schedule-to-bind (SURVEY.md:27; the fleet's ~560µs/pod at 14K/s,
reference README.adoc:783-787).  One operating point says nothing about
the shape: latency at low load shows the floor (batch formation +
device round trip), latency near saturation shows the knee.  This
driver sweeps ``sched_bench --rate`` over a list of offered loads, one
fresh subprocess per point (clean store, clean metrics, compile cache
warm per process), and writes the curve as JSONL plus a markdown table.

    python -m k8s1m_tpu.tools.latency_curve --nodes 1048576 \
        --rates 2000,4000,6000,8000,10000,12000,16000,20000 \
        --out artifacts/latency_curve.jsonl
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="p50-vs-rate curve driver")
    ap.add_argument("--nodes", type=int, default=1_048_576)
    ap.add_argument("--score-pct", type=int, default=5)
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla")
    ap.add_argument(
        "--rates", default="2000,4000,6000,8000,10000,12000,16000,20000",
        help="comma-separated offered loads (pods/s)",
    )
    ap.add_argument(
        "--seconds", type=float, default=12.0,
        help="target measured window per point (pods = rate * seconds)",
    )
    ap.add_argument("--min-pods", type=int, default=20_000)
    ap.add_argument("--out", default="artifacts/latency_curve.jsonl")
    ap.add_argument("--timeout", type=float, default=1800.0,
                    help="per-point subprocess timeout (s)")
    return ap.parse_args(argv)


def run_point(args, rate: int) -> dict | None:
    pods = max(args.min_pods, int(rate * args.seconds))
    cmd = [
        sys.executable, "-m", "k8s1m_tpu.tools.sched_bench",
        "--nodes", str(args.nodes), "--pods", str(pods),
        "--rate", str(rate), "--score-pct", str(args.score_pct),
        "--backend", args.backend,
    ]
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            cmd, stdout=subprocess.PIPE, text=True, timeout=args.timeout
        )
    except subprocess.TimeoutExpired:
        # Record the point as failed but keep the sweep going — the
        # remaining rates still produce a curve.
        print(f"# rate={rate}: timeout after {args.timeout:.0f}s",
              file=sys.stderr)
        return None
    if proc.returncode != 0:
        print(f"# rate={rate}: rc={proc.returncode}", file=sys.stderr)
        return None
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    det = doc["detail"]
    return {
        "rate": rate,
        "pods": pods,
        "binds_per_sec": det["binds_per_sec"],
        "p50_ms": det["p50_ms"],
        "p95_ms": det["p95_ms"],
        "p99_ms": det["p99_ms"],
        "bound": det["bound"],
        "point_wall_s": round(time.perf_counter() - t0, 1),
    }


def main(argv=None):
    args = parse_args(argv)
    rates = [int(r) for r in args.rates.split(",") if r]
    rows = []
    with open(args.out, "w") as f:
        for rate in rates:
            row = run_point(args, rate)
            if row is None:
                continue
            rows.append(row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(f"# rate={rate}: p50={row['p50_ms']}ms "
                  f"p99={row['p99_ms']}ms ach={row['binds_per_sec']}/s",
                  file=sys.stderr)
    # Markdown table for PARITY.
    print("| offered pods/s | achieved binds/s | p50 ms | p95 ms | p99 ms |")
    print("|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['rate']} | {r['binds_per_sec']} | {r['p50_ms']} "
              f"| {r['p95_ms']} | {r['p99_ms']} |")


if __name__ == "__main__":
    main()
