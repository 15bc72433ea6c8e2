"""Unified kernel benchmark driver: sweep, validate, record, self-gate.

Runs the ``repro.bench`` autotuner over every registered kernel family and
emits ``BENCH_kernels.json`` — per (kernel, shape, dtype): the best
validated :class:`BlockConfig`, median us/call (+ the min-max sample
spread), analytic GFLOP/s, the analytic HBM traffic at that config (the
Table-III 'memory access' analogue, via
:func:`repro.core.apr.reduction_hbm_traffic`), and the ``repro.cost``
prediction for the winner.  The JSON schema (v2) is documented in
``benchmarks/README.md``.

With pruning on (the default) every shape is swept twice: exhaustively,
then with cost-model pruning (only the predicted-cheapest K candidates are
timed).  The run **gates itself**: it exits non-zero unless (i) the pruned
sweep picks the exhaustive winner for every shape — literally the same
config, a predicted tie within 1%, or a measured time within the recorded
timer spreads — and (ii) the pruned sweeps time >= 2x fewer candidates in
aggregate.  Per-family predicted-vs-measured error lands in the report, so
the analytic model is re-validated against the very sweep it prunes on
every CI run.  ``--no-prune`` reverts to the single exhaustive sweep.

Usage::

    python benchmarks/bench_kernels.py --quick            # tiny shapes, CI
    python benchmarks/bench_kernels.py                    # full suite
    python benchmarks/bench_kernels.py --out /tmp/b.json --cache /tmp/tc.json

Off-TPU the kernels run in Pallas interpret mode, so absolute times are a
correctness-path proxy (the ``backend`` field records this — interpret-mode
``prediction_error`` is similarly a proxy; on TPU it measures the model);
relative ordering still exercises the full tune/prune/cache plumbing.
Tuned winners also land in the shared config cache, so every later
``repro.kernels`` call site picks them up automatically.
"""
import argparse
import datetime
import json
import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
if str(_REPO / "src") not in sys.path:
    sys.path.insert(0, str(_REPO / "src"))

SCHEMA_VERSION = 2

#: pruned sweeps must time at least this factor fewer candidates than the
#: exhaustive sweeps, summed over the whole suite (the CI gate)
PRUNE_SPEEDUP_GATE = 2.0

# Per-family benchmark shapes.  quick: small enough for interpret-mode CI;
# full: LM-layer-sized geometries (run these on real hardware).
SUITES = {
    "quick": {
        "apr_matmul": [{"m": 64, "k": 128, "n": 64}],
        "apr_matmul_fused": [{"m": 64, "k": 128, "n": 64}],
        "quant_matmul": [{"m": 64, "k": 128, "n": 64}],
        "quant_matmul_fused": [{"m": 64, "k": 128, "n": 64}],
        "apr_conv": [{"b": 1, "h": 8, "w": 8, "c": 4, "hf": 3, "wf": 3,
                      "m": 8, "stride": 1, "padding": 1}],
        "apr_conv_fused": [{"b": 1, "h": 8, "w": 8, "c": 4, "hf": 3, "wf": 3,
                            "m": 8, "stride": 1, "padding": 1}],
        "flash_decode": [{"b": 2, "hq": 4, "hkv": 2, "d": 32, "s": 128}],
        "flash_decode_paged": [{"b": 2, "hq": 4, "hkv": 2, "d": 32,
                                "pages": 4, "ps": 32},
                               {"b": 2, "hq": 4, "hkv": 2, "d": 32,
                                "pages": 4, "ps": 32, "kv_int8": 1}],
        "mamba2": [{"b": 1, "t": 32, "h": 2, "p": 8, "n": 8}],
        "rwkv6": [{"b": 1, "t": 32, "h": 2, "d": 8}],
    },
    "full": {
        "apr_matmul": [
            {"m": 256, "k": 512, "n": 256},
            {"m": 512, "k": 2048, "n": 512},
        ],
        "apr_matmul_fused": [
            {"m": 256, "k": 512, "n": 256},
            {"m": 512, "k": 2048, "n": 512},
        ],
        "quant_matmul": [
            {"m": 256, "k": 512, "n": 256},
            {"m": 512, "k": 2048, "n": 512},
        ],
        "quant_matmul_fused": [
            {"m": 256, "k": 512, "n": 256},
        ],
        "apr_conv": [
            # LeNet conv2-sized im2col (the paper's benchmark operator)
            {"b": 4, "h": 14, "w": 14, "c": 6, "hf": 5, "wf": 5,
             "m": 16, "stride": 1, "padding": 0},
        ],
        "apr_conv_fused": [
            {"b": 4, "h": 14, "w": 14, "c": 6, "hf": 5, "wf": 5,
             "m": 16, "stride": 1, "padding": 0},
        ],
        "flash_decode": [
            {"b": 4, "hq": 8, "hkv": 4, "d": 64, "s": 1024},
        ],
        "flash_decode_paged": [
            {"b": 4, "hq": 8, "hkv": 4, "d": 64, "pages": 8, "ps": 128},
            {"b": 4, "hq": 8, "hkv": 4, "d": 64, "pages": 8, "ps": 128,
             "kv_int8": 1},
        ],
        "mamba2": [
            {"b": 2, "t": 256, "h": 4, "p": 32, "n": 16},
        ],
        "rwkv6": [
            {"b": 2, "t": 256, "h": 4, "d": 32},
        ],
    },
}


def prune_top_k(n_candidates: int) -> int:
    """How many predicted-cheapest candidates a pruned sweep times: half
    the space, capped at 3 (large full-suite spaces), floored at 1."""
    return max(1, min(n_candidates // 2, 3))


def _configs_match(res_pruned, res_exhaustive, predicted_us) -> str:
    """'' when the pruned sweep's pick agrees with the exhaustive one,
    else a reason string.  Agreement = same config, a cost-model tie
    (predictions within 1% — either is a legitimate winner), or measured
    times within the two runs' recorded timer spreads (interpret-mode
    timing noise, not a mis-ranking)."""
    if not res_pruned.ok or not res_exhaustive.ok:
        return "a sweep found no valid config"
    if res_pruned.config == res_exhaustive.config:
        return ""
    p_pr = predicted_us.get(res_pruned.config)
    p_ex = predicted_us.get(res_exhaustive.config)
    if p_pr is not None and p_ex is not None \
            and abs(p_pr - p_ex) <= 0.01 * max(p_pr, p_ex):
        return ""
    if abs(res_pruned.us - res_exhaustive.us) \
            <= res_pruned.spread_us + res_exhaustive.spread_us:
        return ""
    return (f"pruned pick {res_pruned.config.to_dict()} ({res_pruned.us:.1f}"
            f"us) vs exhaustive {res_exhaustive.config.to_dict()} "
            f"({res_exhaustive.us:.1f}us) beyond spread")


def bench_all(*, quick: bool = False, dtype: str = "float32",
              cache_path=None, iters=None, warmup=None,
              max_candidates=None, prune: bool = True):
    import jax

    from repro.bench import ConfigCache, all_specs, autotune, default_cache
    from repro.cost import get_profile, rank_candidates

    cache = ConfigCache(cache_path) if cache_path else default_cache()
    suite = SUITES["quick" if quick else "full"]
    if quick and max_candidates is None:
        max_candidates = 4

    report = {
        "schema_version": SCHEMA_VERSION,
        "generated_at": datetime.datetime.now().isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "mode": "quick" if quick else "full",
        "dtype": dtype,
        "profile": get_profile().name,
        "kernels": {},
        "prediction_error": {},
    }
    timed_exhaustive = 0
    timed_pruned = 0
    parity_failures = []
    for name, spec in sorted(all_specs().items()):
        entries = []
        family_errs = []
        for shape in suite.get(name, []):
            kw = dict(dtype=dtype, cache=cache, iters=iters, warmup=warmup,
                      max_candidates=max_candidates)
            if prune:
                res_ex = autotune(spec, shape, **kw)
                cands = spec.candidates(shape)[:max_candidates]
                k = prune_top_k(len(cands))
                res = autotune(spec, shape, prune_top_k=k, **kw)
                predicted = {cfg: est.predicted_us for cfg, est
                             in rank_candidates(spec, shape, cands)}
                timed_exhaustive += res_ex.n_timed
                timed_pruned += res.n_timed
                mismatch = _configs_match(res, res_ex, predicted)
                if mismatch:
                    parity_failures.append(f"{name}/{res.shape_key}: "
                                           f"{mismatch}")
                pruning = {
                    "match": not mismatch,
                    "timed": res.n_timed,
                    "timed_exhaustive": res_ex.n_timed,
                    "exhaustive_config": (res_ex.config.to_dict()
                                          if res_ex.ok else None),
                    "exhaustive_us": round(res_ex.us, 2)
                    if res_ex.ok else None,
                }
            else:
                res = autotune(spec, shape, **kw)
                pruning = None
            if res.ok and res.predicted_us is not None:
                family_errs.append(abs(res.predicted_us - res.us)
                                   / max(res.us, 1e-9))
            entries.append({
                "shape": dict(shape),
                "shape_key": res.shape_key,
                "dtype": res.dtype,
                "best_config": res.config.to_dict() if res.ok else None,
                "us_per_call": round(res.us, 2) if res.ok else None,
                "spread_us": round(res.spread_us, 2) if res.ok else None,
                "predicted_us": (round(res.predicted_us, 4)
                                 if res.predicted_us is not None else None),
                "gflops": round(res.gflops, 4) if res.ok else None,
                "hbm_bytes_analytic": res.hbm_bytes,
                "n_candidates": res.n_candidates,
                "n_timed": res.n_timed,
                "pruned_from": res.pruned_from,
                "n_rejected": len(res.rejected),
                "pruning": pruning,
            })
        report["kernels"][name] = entries
        if family_errs:
            report["prediction_error"][name] = round(
                sum(family_errs) / len(family_errs), 4)
    if prune:
        speedup = timed_exhaustive / max(timed_pruned, 1)
        report["pruning_gate"] = {
            "timed_exhaustive": timed_exhaustive,
            "timed_pruned": timed_pruned,
            "speedup": round(speedup, 3),
            "speedup_required": PRUNE_SPEEDUP_GATE,
            "config_parity": not parity_failures,
            "parity_failures": parity_failures,
            "passed": (not parity_failures
                       and speedup >= PRUNE_SPEEDUP_GATE - 1e-9),
        }
    return report


def run(csv: bool = False, quick: bool = True):
    """benchmarks/run.py integration: quick sweep, CSV row per kernel."""
    report = bench_all(quick=quick)
    rows = []
    for name, entries in sorted(report["kernels"].items()):
        for e in entries:
            if e["best_config"] is None:
                continue
            cfg = "/".join(f"{k}={v}" for k, v in sorted(e["best_config"].items()))
            rows.append(f"bench_kernels.{name}.{e['shape_key']},"
                        f"{e['us_per_call']:.2f},"
                        f"gflops={e['gflops']};cfg={cfg}")
            if not csv:
                print(f"{name:14s} {e['shape_key']:32s} {e['us_per_call']:10.1f}us "
                      f"{e['gflops']:8.3f} GF/s  {cfg}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes + pruned candidate list (CI-sized)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--out", default=str(_REPO / "BENCH_kernels.json"),
                    help="report path (default: repo-root BENCH_kernels.json)")
    ap.add_argument("--cache", default=None,
                    help="tuned-config cache path (default: $REPRO_TUNE_CACHE "
                         "or ~/.cache/repro/tune_cache.json)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timed reps per candidate (default: "
                         "$REPRO_BENCH_ITERS or 3)")
    ap.add_argument("--max-candidates", type=int, default=None)
    ap.add_argument("--no-prune", dest="prune", action="store_false",
                    help="single exhaustive sweep: no cost-model pruning, "
                         "no predicted-vs-measured gate")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    report = bench_all(quick=args.quick, dtype=args.dtype,
                       cache_path=args.cache, iters=args.iters,
                       max_candidates=args.max_candidates, prune=args.prune)
    out = Path(args.out)
    out.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")

    n = sum(len(v) for v in report["kernels"].values())
    print(f"wrote {out} ({n} entries, backend={report['backend']}, "
          f"mode={report['mode']}, profile={report['profile']})")
    for name, entries in sorted(report["kernels"].items()):
        for e in entries:
            status = (f"{e['us_per_call']:.1f}us {e['gflops']:.3f} GF/s "
                      f"cfg={e['best_config']}"
                      if e["best_config"] is not None else "NO VALID CONFIG")
            if e["pruned_from"]:
                status += (f"  [timed {e['n_timed']}/{e['pruned_from']}, "
                           f"predicted {e['predicted_us']}us]")
            print(f"  {name:14s} {e['shape_key']:36s} {status}")
    gate = report.get("pruning_gate")
    if gate is not None:
        print(f"pruning gate: timed {gate['timed_pruned']} vs "
              f"{gate['timed_exhaustive']} exhaustive "
              f"({gate['speedup']:.2f}x >= {gate['speedup_required']:.1f}x), "
              f"config parity: {gate['config_parity']}")
        for f in gate["parity_failures"]:
            print(f"  PARITY FAIL {f}")
        if not gate["passed"]:
            sys.exit(1)


if __name__ == "__main__":
    main()
