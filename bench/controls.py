#!/usr/bin/env python3
"""The readings the correctness limits are set from, at a cell's own size.

    python3 bench/controls.py --workload wiki1-closed --seeds 1-12 \\
        --control-seeds 1-3 --seconds 4

For every seed, one sound run of the program: the cell's traffic for a short
window at its own load, and the sampled answers held to the plain reference
(the lower readings).  For every control seed, on the same sampled queries,
two controls (the upper readings):

* ``bf16``: the reference itself, computed in bfloat16 (the precision below
  the configuration's float32), put in the program's place;
* ``int8``: the program's own lower-precision phase-1 path, the
  ``fused_int8`` engine, serving the same queries from the same index.

Every control must come out not correct.  One JSON line per seed, then a
summary line: per number, the largest sound reading and the smallest
control reading.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeds(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def control_hooks(cfg: dict):
    """(on_program, on_reference) that read both controls."""
    from bench import check

    def on_program(system, queries):
        from repro.core import TrimFilter
        from repro.serve.engine import BatchedSearchEngine

        eng = BatchedSearchEngine(
            system.index, batch_size=cfg["batch_size"],
            max_wait_s=cfg["max_wait_s"], k=cfg["k"], page=cfg["page"],
            trim=TrimFilter(cfg["trim"]), engine="fused_int8",
            merge=cfg["merge"])
        try:
            res = [f.result(timeout=600) for f in
                   [eng.submit(q) for q in queries]]
        finally:
            eng.close()
        return {"_int8": (np.stack([r[0] for r in res]),
                          np.stack([r[1] for r in res]))}

    def on_reference(ref, queries, readings):
        ids8, sc8 = readings.pop("_int8")
        low = type(ref)(cfg, ref.seed, precision="bfloat16")
        ids16, sc16 = low.answer(queries)
        del low
        return {"control_int8": check.compare(ref, queries, ids8, sc8, cfg),
                "control_bf16": check.compare(ref, queries, ids16, sc16,
                                              cfg)}

    return on_program, on_reference


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import registry

    registry.prepare_env()
    from bench import check, harness

    spec = registry.resolve(args.workload, registry.load_benchmark())
    import jax

    if jax.devices()[0].platform != "tpu":
        print("controls: no TPU", file=sys.stderr)
        return 3
    cfg = spec["config"]
    hooks = control_hooks(cfg)
    worst: dict = {}
    for seed in args.seeds:
        ctl = seed in args.control_seeds
        out = harness.run_cell(
            spec, seed, args.seconds, False, t_start=time.perf_counter(),
            name=f"controls-{seed}",
            on_program=hooks[0] if ctl else None,
            on_reference=hooks[1] if ctl else None)
        line = {"seed": seed, "correct": out["correct"],
                "program": {k: v["value"] for k, v in out["checks"].items()}}
        line.update(out.get("readings", {}))
        for kind, nums in line.items():
            if kind in ("seed", "correct"):
                continue
            for name in cfg["limits"]:
                if name in nums:
                    v = nums[name]
                    key = (kind, name)
                    pick = max if kind == "program" else min
                    worst[key] = v if key not in worst else pick(worst[key], v)
        for kind in ("control_bf16", "control_int8"):
            if kind in line:
                line[kind + "_correct"] = check.verdict(
                    line[kind], cfg["limits"])[0]
        print(json.dumps(line), flush=True)
    summary = {f"{kind}.{name}": v for (kind, name), v in sorted(worst.items())}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
