"""Recurrent-family serving benchmark: paged state cache vs slot engine.

Drives one request trace per recurrent-state family — rwkv6 (linear
attention), mamba2 (SSD), zamba2 (hybrid: attention pages + mamba state
slots in one cache) — through the contiguous slot engine and through
``PagedServeEngine`` backed by ``repro.serve.state_cache``, and writes
``BENCH_state.json`` (schema in benchmarks/README.md).  Exits non-zero
unless, for every family, the paged engine's greedy outputs are
**token-identical** to the slot engine's and int8 state storage cuts
state-pool bytes by **>= 1.5x** (the CI gate).

Per family the report carries:

* ``slot`` / ``paged`` — wall-clock + phase-local throughput for both
  engines (the paged side reports prefill/decode tok/s from
  ``EngineMetrics``),
* ``tokens_identical`` — the greedy identity gate,
* ``state_pool_bytes_fp32`` vs ``state_pool_bytes_int8`` — the state-pool
  footprint at both storage dtypes (``state_dtype="int8"`` stores the
  large wkv/ssm running-reduction leaves int8 + per-head scales).  int8
  state is **lossy across steps** (re-quantized every token, unlike int8
  KV), so the int8 run's identity is reported (``tokens_identical_int8``)
  but deliberately **not** gated.

    PYTHONPATH=src python benchmarks/bench_state.py --quick
"""
import argparse
import datetime
import json
import sys
import time
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
for _p in (str(_REPO / "src"), str(_REPO / "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from _serve_common import request_trace as _trace  # noqa: E402
from _serve_common import warm_engine  # noqa: E402

SCHEMA_VERSION = 1

#: int8 state storage must cut state-pool bytes by at least this much
#: (wkv/ssm go 4x; the conv-window / token-shift leaves stay native)
MIN_STATE_BYTES_REDUCTION = 1.5

#: the recurrent-state families the StateCache serves (ssm / mamba /
#: hybrid); zamba2 is the mixed case — KV pages AND state slots
FAMILY_ARCHS = ("rwkv6-3b", "mamba2-2.7b", "zamba2-1.2b")


def _state_pool_bytes(engine) -> int:
    from repro.models.paged_state import STATE_POOL_KEYS
    return sum(int(a.size) * a.dtype.itemsize
               for k, a in engine.cache.items() if k in STATE_POOL_KEYS)


def _run_slot(bundle, params, pctx, reqs, *, slots, max_seq):
    from repro.serve import ServeEngine
    eng = ServeEngine(bundle, params, pctx, slots=slots, max_seq=max_seq)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run_until_drained()
    dt = time.perf_counter() - t0
    total = sum(len(r.output) for r in reqs)
    return {"elapsed_s": round(dt, 4), "total_tokens": total,
            "tokens_per_s": round(total / max(dt, 1e-9), 2),
            "outputs": [r.output for r in reqs]}


def _run_paged(bundle, params, pctx, reqs, *, slots, page_size,
               prefill_chunk, state_dtype):
    from repro.serve import PagedServeEngine
    eng = PagedServeEngine(bundle, params, pctx, slots=slots,
                           page_size=page_size, prefill_chunk=prefill_chunk,
                           state_dtype=state_dtype)
    warm_engine(eng, prompt_len=prefill_chunk + 1)
    for r in reqs:
        eng.submit(r)
    m = eng.run_until_drained()
    out = {k: m.summary()[k] for k in
           ("requests_done", "prefill_tokens", "decode_tokens",
            "prefill_tps", "decode_tps")}
    out["state_pool_bytes"] = _state_pool_bytes(eng)
    out["cache_pool_bytes"] = eng.kv_pool_bytes()
    out["state_pool_slots"] = eng.state.pool_slots
    out["outputs"] = [r.output for r in reqs]
    assert eng.state.used_slots == 0 and eng.kv.used_pages == 0, \
        "drained engine must leak no state slots or KV pages"
    return out


def bench_family(arch, pctx, *, requests, prompt_len, max_new, slots,
                 page_size, prefill_chunk):
    import jax

    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(arch, smoke=True)
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))

    run_trace = lambda: _trace(requests, prompt_len, max_new)
    slot = _run_slot(bundle, params, pctx, run_trace(), slots=slots,
                     max_seq=max(128, prompt_len + max_new + 2))
    paged = _run_paged(bundle, params, pctx, run_trace(), slots=slots,
                       page_size=page_size, prefill_chunk=prefill_chunk,
                       state_dtype="float32")
    int8 = _run_paged(bundle, params, pctx, run_trace(), slots=slots,
                      page_size=page_size, prefill_chunk=prefill_chunk,
                      state_dtype="int8")
    ref = slot.pop("outputs")
    return {
        "family": cfg.family,
        "slot": slot,
        "paged": paged,
        "tokens_identical": paged.pop("outputs") == ref,
        "state_pool_bytes_fp32": paged["state_pool_bytes"],
        "state_pool_bytes_int8": int8["state_pool_bytes"],
        "state_bytes_reduction": round(
            paged["state_pool_bytes"] / max(int8["state_pool_bytes"], 1), 3),
        # int8 state is lossy across steps: reported, never gated
        "tokens_identical_int8": int8.pop("outputs") == ref,
        "decode_tps_int8": int8["decode_tps"],
    }


def bench(*, quick: bool, requests: int, prompt_len: int, max_new: int,
          slots: int, page_size: int, prefill_chunk: int):
    import jax

    from repro.parallel.sharding import ParallelContext

    pctx = ParallelContext(None)
    families = {arch: bench_family(
        arch, pctx, requests=requests, prompt_len=prompt_len,
        max_new=max_new, slots=slots, page_size=page_size,
        prefill_chunk=prefill_chunk) for arch in FAMILY_ARCHS}

    return {
        "schema_version": SCHEMA_VERSION,
        "generated_at": datetime.datetime.now().isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "mode": "quick" if quick else "full",
        "workload": {"requests": requests, "prompt_len": prompt_len,
                     "max_new": max_new, "slots": slots,
                     "page_size": page_size, "prefill_chunk": prefill_chunk},
        "families": families,
        "outputs_identical": all(f["tokens_identical"]
                                 for f in families.values()),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized trace (what the workflow runs)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--out", default=str(_REPO / "BENCH_state.json"))
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    defaults = ((3, 24, 6) if args.quick else (6, 48, 12))
    requests = args.requests or defaults[0]
    prompt_len = args.prompt_len or defaults[1]
    max_new = args.max_new or defaults[2]

    report = bench(quick=args.quick, requests=requests,
                   prompt_len=prompt_len, max_new=max_new, slots=args.slots,
                   page_size=args.page_size,
                   prefill_chunk=min(args.prefill_chunk, prompt_len))
    Path(args.out).write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")

    print(f"wrote {args.out} (backend={report['backend']})")
    ok = True
    for arch, f in report["families"].items():
        print(f"  {arch} ({f['family']}): paged identical="
              f"{f['tokens_identical']} decode {f['paged']['decode_tps']} "
              f"tok/s (slot {f['slot']['tokens_per_s']} tok/s wall); state "
              f"pool {f['state_pool_bytes_fp32']}B fp32 -> "
              f"{f['state_pool_bytes_int8']}B int8 "
              f"({f['state_bytes_reduction']:.2f}x; int8 identical="
              f"{f['tokens_identical_int8']}, ungated)")
        ok &= f["tokens_identical"]
        ok &= f["state_bytes_reduction"] >= MIN_STATE_BYTES_REDUCTION
    if not ok:
        print(f"FAIL: every family must be token-identical to the slot "
              f"engine and int8 state must cut state-pool bytes >= "
              f"{MIN_STATE_BYTES_REDUCTION}x", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
