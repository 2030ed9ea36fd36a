#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload wiki1-closed --seed 7 --seconds 30 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness comparison
held to its limit.  The same checks close standard error.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for, or when the program cannot be imported.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import registry

    registry.prepare_env()
    from bench import harness
    try:
        spec = registry.resolve(args.workload, registry.load_benchmark())
        import repro.serve.engine  # noqa: F401 - the system under test
    except (OSError, KeyError, ValueError, ImportError) as e:
        print(f"bench: cannot set up {args.workload!r}: {e!r}",
              file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    need = spec["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"bench: {args.workload} needs {need} TPU chip(s); JAX found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        return 3
    out = harness.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START,
                           name=f"{args.workload}-{args.seed}")
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
