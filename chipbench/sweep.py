#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate at which the
waiting queue does not grow across the window.

    python chipbench/sweep.py --workload chatglm3-6b.chat \\
        --rates 1.0,1.5,2.0,2.5 --seconds 30 --seed 5

One process, one set of weights; a fresh engine for each rate, which is
the cell's traffic with only ``rate_per_s`` replaced.  One JSON line per
rate: the waiting queue at the window's open and close and its slope,
output tokens/s, the TTFT and inter-token tails.
"""
import argparse
import asyncio
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from chipbench import cell as cellmod
    from chipbench import harness, window
    from chipbench.run import use_compile_cache

    import jax
    import numpy as np
    from repro.parallel.sharding import ParallelContext
    from repro.serve import PagedServeEngine

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    cell = cellmod.load(ROOT, args.workload)
    fam = cellmod.family(cell)
    cfg, sizes = harness.model_sizes(cell)
    first, _ = harness.build(cell, cfg, sizes, fam, args.seed)
    bundle, params = first.bundle, first.params
    del first
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        engine = PagedServeEngine(bundle, params, ParallelContext(None),
                                  **cell.traffic["engine"])
        harness.warm_up(engine)
        samples = []

        async def watch(w_close):
            while time.perf_counter() < w_close + 1.0:
                samples.append((time.perf_counter(), len(engine.sched.waiting)))
                await asyncio.sleep(0.25)

        async def both():
            w_open = time.perf_counter() + cell.traffic["warmup_s"]
            task = asyncio.ensure_future(watch(w_open + args.seconds))
            out = await harness.drive(engine, cell, args.seed, args.seconds,
                                      sizes["vocab_size"], None, drain_s=0.0)
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
            return out

        records, w = asyncio.run(both())
        inside = [(t, q) for t, q in samples if w.holds(t)]
        ts, qs = np.array(inside).T if inside else (np.zeros(1), np.zeros(1))
        slope = float(np.polyfit(ts - ts[0], qs, 1)[0]) if len(ts) > 2 else 0.0
        tt = window.ttfts(records, w)
        gaps = window.itl_gaps(records, w)
        print(json.dumps({
            "rate_per_s": rate, "queue_open": int(qs[0]),
            "queue_close": int(qs[-1]), "queue_max": int(qs.max()),
            "queue_slope_per_s": slope,
            "output_tok_s": window.output_tok_s(records, w),
            "ttft_p50_ms": 1e3 * window.percentile(tt, 50),
            "ttft_p90_ms": 1e3 * window.percentile(tt, 90),
            "itl_p95_ms": 1e3 * window.percentile(gaps, 95) if gaps else None,
            "requests_due": len(window.due_in(records, w)),
            "unanswered": len(window.failed(records, w))}), flush=True)
        del engine
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
