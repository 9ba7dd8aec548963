"""Find the knee once: one set-up, then an open-loop window at each rate.

    python3 benchmarks/sweep.py --workload <open cell> --rates 200,400,... --seconds 10

Not a benchmark run: it prints, for each rate, the latency percentiles, the
requests still unanswered when the window closed (the backlog), the sheds,
and how late the generator ran. ``PERF.md`` records what it found; the rate
the cell then offers is a number in its traffic file.
"""

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--device", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args()

    from benchmarks import run as run_mod
    from benchmarks.harness.manifest import Cell, load_manifest, load_module
    from benchmarks.harness.stats import latencies_ms, percentile

    bench_dir = os.path.join(ROOT, "benchmarks")
    cell = Cell(load_manifest(ROOT), args.workload, bench_dir)
    if args.device == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
    run_mod._device_block(args.device, cell.chips)
    ctx = run_mod.Context(cell, args, bench_dir)
    driver = load_module("drivers", cell.config["driver"], bench_dir)
    st = driver.setup(ctx)
    rows = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(ctx.sized(cell.traffic), rate_per_s=rate)
            ctx.seed = args.seed + i
            w = driver.window(ctx, st, mix, args.seconds, first=(i == 0))
            req = w["requests"]
            lat = latencies_ms(req, float(mix["timeout_s"]) * 1e3)
            t_end = w["t_start"] + args.seconds
            backlog = w["expected"] - sum(
                1 for d in req["done"] if d is not None and d <= t_end)
            # does latency grow through the window? first against last third
            third = max(1, len(lat) // 3)
            row = {
                "rate": rate, "expected": w["expected"], "ok": w["ok"],
                "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
                "p99_ms": percentile(lat, 99), "max_ms": max(lat),
                "generator_late_ms_max": w["info"]["generator_late_ms_max"],
                "p50_first_third_ms": percentile(lat[:third], 50),
                "p50_last_third_ms": percentile(lat[-third:], 50),
                "backlog_at_close": backlog,
                "shed": w["counters"]["oryx_shed_requests_total"],
                "deadline_flushes": w["counters"][
                    "oryx_coalescer_deadline_flushes_total"],
                "generator_late_ms_p99": w["info"]["generator_late_ms_p99"],
                "compiles": w["compiles"],
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        driver.teardown(st)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sweep.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
