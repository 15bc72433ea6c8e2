"""Serving benchmark: chunked prefill vs token-by-token, paged vs slot.

Drives the same request trace through three engine configurations —

* ``paged_chunked``  — PagedServeEngine, prefill_chunk > 1 (the production
  configuration: one multi-token ``decode_paged`` call per chunk),
* ``paged_token``    — PagedServeEngine, prefill_chunk = 1 (token-by-token
  prefill over the *same* paged cache: isolates the chunking win from the
  paging change),
* ``slot``           — the contiguous-cache seed engine (prefills through
  the decode path; timed with the same wall clock for reference)

— and writes ``BENCH_serve.json`` (schema in benchmarks/README.md).  The
headline number is prefill tokens/s: chunked prefill amortises one model
invocation over ``prefill_chunk`` prompt tokens, so it must beat the
token-by-token loop.

A fourth section, ``prefix_cache``, drives a shared-system-prompt workload
(every prompt = one common head + a per-request tail) through the paged
engine with ``prefix_sharing`` off and on: outputs must stay token-identical
and the sharing run reports its **effective-KV-capacity multiplier** —
logical prompt pages admitted per physical page materialized (the gate
requires >= 2x; with sharing off the same workload sits at ~1x).

    PYTHONPATH=src python benchmarks/bench_serve.py --quick
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
from _serve_common import shared_prefix_trace, warm_engine  # noqa: E402

SCHEMA_VERSION = 2

#: the prefix-cache gate: the shared-prompt workload must show at least
#: this effective-KV-capacity multiplier with sharing on
MIN_KV_MULTIPLIER = 2.0


def _run_paged(bundle, params, pctx, reqs, *, slots, page_size, prefill_chunk):
    from repro.serve import PagedServeEngine
    eng = PagedServeEngine(bundle, params, pctx, slots=slots,
                           page_size=page_size, prefill_chunk=prefill_chunk)
    # warm the jit caches (prefill-chunk + decode shapes) so the timed trace
    # measures steady-state serving, not XLA compilation
    warm_engine(eng, prompt_len=prefill_chunk + 1)
    for r in reqs:
        eng.submit(r)
    m = eng.run_until_drained()
    out = m.summary()
    out["outputs"] = [r.output for r in reqs]
    return out


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


def _run_prefix_cache(bundle, params, pctx, *, requests, shared_len,
                      unique_len, max_new, slots, page_size, prefill_chunk):
    """Shared-system-prompt workload, sharing off vs on, same trace."""
    from repro.serve import PagedServeEngine

    def run(sharing):
        eng = PagedServeEngine(bundle, params, pctx, slots=slots,
                               page_size=page_size,
                               prefill_chunk=prefill_chunk,
                               prefix_sharing=sharing)
        warm_engine(eng, prompt_len=prefill_chunk + 1)
        reqs = shared_prefix_trace(requests, shared_len, unique_len, max_new)
        for r in reqs:
            eng.submit(r)
        m = eng.run_until_drained()
        return [r.output for r in reqs], m

    out_off, m_off = run(sharing=False)
    out_on, m_on = run(sharing=True)
    return {
        "workload": {"requests": requests, "shared_len": shared_len,
                     "unique_len": unique_len, "max_new": max_new},
        "outputs_identical": out_on == out_off,
        "effective_kv_multiplier": round(m_on.effective_kv_multiplier, 3),
        "effective_kv_multiplier_off": round(m_off.effective_kv_multiplier,
                                             3),
        "prompt_pages_logical": m_on.prompt_pages_logical,
        "prompt_pages_unique": m_on.prompt_pages_unique,
        "unique_pages_per_request": round(
            m_on.prompt_pages_unique / max(requests, 1), 3),
        "prefix_hit_requests": m_on.prefix_hit_requests,
        "prefix_hit_tokens": m_on.prefix_hit_tokens,
        "cow_copies": m_on.cow_copies,
        "prefill_tokens_on": m_on.prefill_tokens,
        "prefill_tokens_off": m_off.prefill_tokens,
        "min_kv_multiplier": MIN_KV_MULTIPLIER,
    }


def bench(*, arch: str, requests: int, prompt_len: int, max_new: int,
          slots: int, page_size: int, prefill_chunk: int,
          prefix_requests: int, shared_len: int, unique_len: int):
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.parallel.sharding import ParallelContext

    cfg = get_config(arch, smoke=True)
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    pctx = ParallelContext(None)

    chunked = _run_paged(bundle, params, pctx,
                         _trace(requests, prompt_len, max_new),
                         slots=slots, page_size=page_size,
                         prefill_chunk=prefill_chunk)
    token = _run_paged(bundle, params, pctx,
                       _trace(requests, prompt_len, max_new),
                       slots=slots, page_size=page_size, prefill_chunk=1)
    slot = _run_slot(bundle, params, pctx,
                     _trace(requests, prompt_len, max_new),
                     slots=slots, max_seq=max(128, prompt_len + max_new + 2))

    prefix = _run_prefix_cache(bundle, params, pctx,
                               requests=prefix_requests,
                               shared_len=shared_len, unique_len=unique_len,
                               max_new=max_new, slots=slots,
                               page_size=page_size,
                               prefill_chunk=prefill_chunk)

    identical = (chunked.pop("outputs") == token.pop("outputs")
                 == slot.pop("outputs"))
    speedup = chunked["prefill_tps"] / max(token["prefill_tps"], 1e-9)
    return {
        "schema_version": SCHEMA_VERSION,
        "generated_at": datetime.datetime.now().isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "arch": arch,
        "workload": {"requests": requests, "prompt_len": prompt_len,
                     "max_new": max_new, "slots": slots,
                     "page_size": page_size, "prefill_chunk": prefill_chunk},
        "engines": {"paged_chunked": chunked, "paged_token": token,
                    "slot": slot},
        "prefix_cache": prefix,
        "outputs_identical": identical,
        "prefill_chunk_speedup": round(speedup, 3),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized trace (fewer/shorter requests)")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--out", default=str(_REPO / "BENCH_serve.json"))
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    defaults = ((4, 24, 8) if args.quick else (8, 64, 16))
    requests = args.requests or defaults[0]
    prompt_len = args.prompt_len or defaults[1]
    max_new = args.max_new or defaults[2]
    # prefix-cache workload: the shared head spans whole pages so the gate
    # reflects page-level dedup, the unique tail spans one page
    ps = args.page_size
    prefix_requests, shared_len, unique_len = \
        ((6, 3 * ps, ps) if args.quick else (8, 4 * ps, ps))

    report = bench(arch=args.arch, requests=requests, prompt_len=prompt_len,
                   max_new=max_new, slots=args.slots,
                   page_size=args.page_size,
                   prefill_chunk=min(args.prefill_chunk, prompt_len),
                   prefix_requests=prefix_requests, shared_len=shared_len,
                   unique_len=unique_len)
    Path(args.out).write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")

    e = report["engines"]
    p = report["prefix_cache"]
    print(f"wrote {args.out} (backend={report['backend']}, "
          f"outputs_identical={report['outputs_identical']})")
    print(f"  prefill tok/s: chunked={e['paged_chunked']['prefill_tps']:.1f}  "
          f"token-by-token={e['paged_token']['prefill_tps']:.1f}  "
          f"speedup={report['prefill_chunk_speedup']:.2f}x")
    print(f"  decode tok/s:  chunked={e['paged_chunked']['decode_tps']:.1f}  "
          f"ttft p50: {e['paged_chunked']['p50_ttft_s']}s vs "
          f"{e['paged_token']['p50_ttft_s']}s token-by-token")
    print(f"  prefix cache: effective-KV x{p['effective_kv_multiplier']:.2f}"
          f" (off: x{p['effective_kv_multiplier_off']:.2f})  "
          f"{p['prompt_pages_logical']} logical / "
          f"{p['prompt_pages_unique']} unique pages  "
          f"hits={p['prefix_hit_requests']}/"
          f"{p['workload']['requests']} req  cow={p['cow_copies']}")
    failed = False
    if not report["outputs_identical"]:
        print("FAIL: the three engine configurations emitted different "
              "tokens for the same trace", file=sys.stderr)
        failed = True
    if not p["outputs_identical"]:
        print("FAIL: prefix sharing changed the shared-prompt workload's "
              "tokens (must be identical to sharing off)", file=sys.stderr)
        failed = True
    if p["effective_kv_multiplier"] < MIN_KV_MULTIPLIER:
        print(f"FAIL: effective KV multiplier "
              f"{p['effective_kv_multiplier']:.2f}x < "
              f"{MIN_KV_MULTIPLIER}x gate on the shared-prompt workload",
              file=sys.stderr)
        failed = True
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
