"""Benchmark aggregator: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--check]

Default is the quick grid (CPU-friendly); --full runs the complete paper
grids.  Prints ``name,us_per_call,derived`` CSV lines per the scaffold
contract, then the roofline summary from the dry-run artifacts.

One process per device: a process that has touched JAX holds the
accelerator, and a child started after that cannot get it.  So the
device benches run first, each in its own child, while this parent is
still JAX-free; the in-process suites import JAX only after the last
child has exited.

``--check`` runs the perf-regression gate (:mod:`benchmarks.check`)
over the committed ``artifacts/BENCH_*.json`` instead of the suites:
each bench's latest-run headline is compared against its first
committed run (ratio thresholds per metric, explicit SKIP when only one
run exists), the obs-overhead bars and the fused-kernel byte claim are
re-asserted, and the process exits nonzero on any regression -- the
``make bench-check`` entry point.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_device_bench(name: str, grid_args: list, full: bool) -> None:
    """A virtual-device bench (shard_scale / replica_scale) in a subprocess:
    the device fan-out flag must precede jax initialisation.  Each emits
    its artifacts/BENCH_<name>.json."""
    cmd = [sys.executable, "-m", f"benchmarks.{name}"] + grid_args
    if not full:
        # quick-config rows are not comparable to the full trajectory; keep
        # them out of the accumulating BENCH_<name>.json
        cmd += ["--docs", "4000", "--features", "32", "--queries", "32",
                "--json", os.path.join(_ROOT, "artifacts",
                                       f"BENCH_{name}_quick.json")]
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep * bool(env.get("PYTHONPATH")) \
        + env.get("PYTHONPATH", "")
    t0 = time.perf_counter()
    try:
        out = subprocess.run(cmd, cwd=_ROOT, env=env, capture_output=True,
                             text=True, timeout=1800)
    except subprocess.TimeoutExpired:
        print(f"{name},{(time.perf_counter()-t0)*1e6:.0f},FAILED_timeout")
        return
    for line in out.stdout.splitlines():
        if line.startswith(f"{name},"):
            print(line)
    if out.returncode != 0:
        print(f"{name},{(time.perf_counter()-t0)*1e6:.0f},"
              f"FAILED_rc={out.returncode}")
        sys.stderr.write(out.stderr[-2000:])


def main() -> None:
    if "--check" in sys.argv:
        from . import check

        sys.exit(check.main([a for a in sys.argv[1:] if a != "--check"]))
    full = "--full" in sys.argv
    quick = not full
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    from repro.launch.hostdev import use_compile_cache

    use_compile_cache()          # children inherit the cache directory
    print("name,us_per_call,derived")
    _run_device_bench("shard_scale", ["--shards", "1,2,4"], full)
    _run_device_bench("replica_scale", ["--grid", "1x1,2x1,2x2,4x2"], full)
    _run_device_bench("build_scale", ["--shards", "1,2,4"], full)
    _run_device_bench("cluster_scale", ["--grid", "1x1,2x2,4x2",
                                        "--streams", "1,4"], full)
    _run_device_bench("store_scale", ["--shards", "1,4"], full)
    _run_device_bench("segment_scale", ["--shards", "1,4"], full)
    _run_device_bench("obs_overhead", [], full)
    _run_device_bench("profile_overhead", [], full)

    # the parent's first JAX import: no child needs a device after this
    from . import (complexity_probe, fig1_page_sweep, fig2_tradeoff, roofline,
                   table2_quality, table3_speed, table4_mlt)

    t0 = time.perf_counter()
    rows2 = table2_quality.run(quick=quick)
    best = max(r["avg_p10"] for r in rows2 if r["system"] == "encoded")
    mlt = max((r["avg_p10"] for r in rows2 if r["system"] == "MLT"), default=0)
    print(f"table2_quality,{(time.perf_counter()-t0)*1e6:.0f},"
          f"best_avg_p10={best:.4f};mlt_avg_p10={mlt:.4f}")

    t0 = time.perf_counter()
    rows3 = table3_speed.run(quick=quick)
    fastest = min(r["per_query_s"] for r in rows3)
    print(f"table3_speed,{(time.perf_counter()-t0)*1e6:.0f},"
          f"fastest_per_query_s={fastest:.5f}")

    t0 = time.perf_counter()
    rows4 = table4_mlt.run(quick=quick)
    print(f"table4_mlt,{(time.perf_counter()-t0)*1e6:.0f},"
          f"mlt25_per_query_s={rows4[0]['per_query_s']:.5f}")

    t0 = time.perf_counter()
    rows_f1 = fig1_page_sweep.run(quick=quick)
    print(f"fig1_page_sweep,{(time.perf_counter()-t0)*1e6:.0f},rows={len(rows_f1)}")

    t0 = time.perf_counter()
    rows_f2 = fig2_tradeoff.run(quick=quick)
    print(f"fig2_tradeoff,{(time.perf_counter()-t0)*1e6:.0f},rows={len(rows_f2)}")

    t0 = time.perf_counter()
    rows_cp = complexity_probe.run(quick=quick)
    print(f"complexity_probe,{(time.perf_counter()-t0)*1e6:.0f},rows={len(rows_cp)}")

    t0 = time.perf_counter()
    roofline.main(full=full)
    print(f"roofline,{(time.perf_counter()-t0)*1e6:.0f},"
          "see_BENCH_kernel_scale")


if __name__ == "__main__":
    main()
