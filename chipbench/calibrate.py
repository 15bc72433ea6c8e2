#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python chipbench/calibrate.py --workload chatglm3-6b.chat \\
        --seeds 11,12,13 --seconds 20 [--save gaps.json.gz]

In one process, for each seed: the weights from that seed, the cell's
engine and traffic for a short window at the cell's own load, then over
the same sample of served requests that a run checks, the program's
logit gaps against the float32 reference, and the same gaps of the
tokens that the int8 control (the reference one step below bf16) puts
first.  Both go through the run's own comparison (``harness.judge``) with
the cell's limits file: the program has to come out correct and the
control not.  One JSON line per seed; the benchmark's own runs never run
the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", help="write every seed's per-token gaps, "
                    "the program's and the control's, to this .json.gz")
    args = ap.parse_args(argv)
    import numpy as np

    from chipbench import cell as cellmod
    from chipbench import harness, window
    from chipbench.run import use_compile_cache

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    cell = cellmod.load(ROOT, args.workload)
    fam = cellmod.family(cell)
    cfg, sizes = harness.model_sizes(cell)
    saved = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        engine, _ = harness.build(cell, cfg, sizes, fam, seed)
        harness.warm_up(engine)
        records, w = asyncio.run(harness.drive(
            engine, cell, seed, args.seconds, sizes["vocab_size"], None))
        del engine
        gc.collect()
        sample = harness.pick_sample(records, w, seed,
                                     cell.traffic["check_tokens"])
        t1 = time.perf_counter()
        prog, ctl = harness.gap_arrays(fam, sizes, seed, sample,
                                       sizes["vocab_size"], control=True)
        line = {"seed": seed, "tokens": int(prog.size),
                "requests": len(sample), "reference_s": time.perf_counter() - t1,
                "seconds": time.perf_counter() - t0}
        unanswered = len(window.failed(records, w))
        for who, g, bad in (("program", prog, unanswered),
                            ("control", ctl, 0)):
            checks, correct = harness.judge(g, cell.limits, bad)
            line[who] = {"correct": correct,
                         "mean_logit_gap": checks["mean_logit_gap"]["value"],
                         "max_logit_gap": float(np.max(g)) if g.size else None,
                         "flips": int(np.count_nonzero(g))}
        print(json.dumps(line), flush=True)
        saved.append({"seed": seed, "program": prog.tolist(),
                      "control": ctl.tolist()})
    if args.save:
        Path(args.save).parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(args.save, "wt") as f:
            json.dump({"workload": args.workload, "seeds": saved}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
