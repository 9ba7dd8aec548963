"""Shared by the benchmark's tests: run the one command in its ``cpu``
rehearsal, optionally with the timed path broken underneath."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def rehearse(cell, seed=3, seconds=1.5, trace=0, run=RUN, prelude="",
             extra_env=None, timeout=600):
    """(return code, last stdout line parsed or None, stderr). ``prelude`` is
    Python run in the same process before the benchmark's main: the way a
    test breaks the program underneath the harness."""
    code = (
        "import sys, runpy\n"
        f"sys.argv = [{run!r}, '--workload', {cell!r}, '--seed', '{seed}', "
        f"'--seconds', '{seconds}', '--trace', '{trace}', '--device', 'cpu']\n"
        f"sys.path.insert(0, {os.path.dirname(os.path.dirname(run))!r})\n"
        + prelude +
        f"\nrunpy.run_path({run!r}, run_name='__main__')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_ENABLE_X64", None)  # the program runs with x64 off
    env.update(extra_env or {})
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    last = json.loads(lines[-1]) if lines else None
    return p.returncode, last, p.stderr
