"""The chip benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip; load generators are children that never import
jax. The last line of standard output is the result. Without a TPU (or with
fewer chips than the cell asks for) it exits non-zero and prints no result;
``--device cpu`` is the tests' tiny rehearsal of the same body and labels
itself ``cpu``.
"""

import time

_T0 = time.monotonic()  # as early as a Python program can say "started"

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Phases:
    """Set-up by phase, on the host's clock."""

    def __init__(self, t0: float):
        self.last = t0
        self.rows = []

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.rows.append((name, now - self.last))
        self.last = now


class Context:
    def __init__(self, cell, args, bench_dir):
        self.cell, self.bench_dir = cell, bench_dir
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.trace, self.control = bool(args.trace), bool(args.control)
        self.device_mode = args.device
        self.phases = Phases(_T0)
        self.setup_s = None
        self.trace_dir = None
        self.trace_t0 = None

    def sized(self, data: dict) -> dict:
        """The file's sizes; in the ``cpu`` rehearsal, its ``rehearsal``
        overrides on top."""
        out = {k: v for k, v in data.items() if k != "rehearsal"}
        if self.device_mode == "cpu":
            out.update(data.get("rehearsal", {}))
        return out

    def window_opens(self, t_start: float) -> None:
        self.setup_s = t_start - _T0

    def memory_peak(self) -> int:
        import jax

        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else 0

    def start_trace(self) -> str:
        import jax

        self.trace_dir = os.path.join(self.bench_dir, ".trace",
                                      self.cell.name.replace("/", "_"))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.trace_t0 = time.monotonic()
        return self.trace_dir

    def window_annotation(self):
        """Marks the measured window in the trace (a no-op untraced)."""
        import contextlib

        import jax

        if not self.trace:
            return contextlib.nullcontext()
        from benchmarks.harness.trace import WINDOW_ANNOTATION

        return jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()


def _device_block(platform_wanted: str, chips: int) -> dict:
    import jax

    devs = jax.local_devices()
    d0 = devs[0]
    if d0.platform != platform_wanted:
        raise SystemExit(f"jax found no {platform_wanted}: {d0.platform}")
    if platform_wanted == "tpu" and len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, jax found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips if platform_wanted == "tpu" else len(devs)}


def evaluate(cell, specs: list, obs: dict, bench_dir: str,
             on_chip: bool = True) -> dict:
    """Each metric's own reader over what the run observed; a reader that
    finds nothing to read returns None and the metric is left out. A run
    that is not on the chip reports no metric whose source is the device's
    trace: a CPU number never stands under a device metric's name."""
    from benchmarks.harness.manifest import load_module

    out = {}
    for m in specs:
        if not on_chip and m["source"] == "device_trace":
            continue
        spec = cell.metric_spec(m["name"])
        reader = load_module("readers", spec["reader"], bench_dir)
        value = reader.read(obs, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--device", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--control", type=int, default=0,
                    help="also read the lower-precision control (not a "
                         "benchmark run: its numbers join the compared ones)")
    args = ap.parse_args(argv)

    from benchmarks.harness.manifest import Cell, load_manifest, load_module

    bench_dir = os.path.join(ROOT, "benchmarks")
    manifest = load_manifest(ROOT)
    cell = Cell(manifest, args.workload, bench_dir)
    if args.device == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        # a rehearsal leaves nothing in the checkout's compile cache
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
    device = _device_block(args.device, cell.chips)
    ctx = Context(cell, args, bench_dir)
    driver = load_module("drivers", cell.config["driver"], bench_dir)
    result = driver.run(ctx)

    obs = result["obs"]
    obs["setup_s"] = ctx.setup_s
    obs["phases"] = ctx.phases.rows
    obs["device_kind"] = device["kind"]
    obs["bench_dir"] = bench_dir
    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": False, "attempted": result["attempted"],
            "failed": result["failed"]}
    if ctx.trace:
        from benchmarks.harness import trace as trace_mod

        if os.environ.get("ORYX_BENCH_DESCRIBE_TRACE"):
            # by hand only: a listing of the trace beside the result
            with open(os.environ["ORYX_BENCH_DESCRIBE_TRACE"], "w") as f:
                f.write(trace_mod.describe(
                    trace_mod._find_xplane(obs["trace_dir"])))
        reduced = trace_mod.reduce_dir(obs["trace_dir"])
        obs["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["metrics"] = evaluate(cell, cell.per_layer, obs, bench_dir,
                                   args.device == "tpu")
        line["breakdown"] = trace_mod.breakdown(reduced, obs)
        shutil.rmtree(obs["trace_dir"], ignore_errors=True)
    else:
        line["metrics"] = evaluate(cell, cell.end_to_end, obs, bench_dir)
    line["device"] = device
    checks = result["checks"]
    line["correct"] = checks.correct
    line["compared"] = checks.as_dict()  # last, as the contract asks
    print(json.dumps({"info": "setup", "setup_s": ctx.setup_s,
                      "phases": ctx.phases.rows}), file=sys.stderr)
    checks.print_last()
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
