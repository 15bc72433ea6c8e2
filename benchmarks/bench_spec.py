"""Speculative-decoding benchmark: draft-and-verify vs the plain paged engine.

Drives one request trace through the plain ``PagedServeEngine`` and through
``SpeculativeServeEngine`` (``repro.spec``) at several K (drafted tokens
verified per step) with both proposers — the n-gram self-draft and, when a
draft config is registered for the arch, the paired draft model — and
writes ``BENCH_spec.json`` (schema in benchmarks/README.md).

Two things are measured per configuration:

* **Correctness** (the CI gate): speculative greedy outputs must be
  token-identical to the plain engine's for every request at every K — the
  process exits non-zero otherwise.
* **Throughput**: decode tokens/s *including draft time*
  (``spec_decode_tps``) against the plain engine's ``decode_tps``, plus the
  acceptance rate and tokens emitted per verify step that explain it.  The
  headline (``best_speedup``) is the best ratio across configurations; the
  ISSUE-4 acceptance bar is >= 1.5x at some K.

Engines are warmed with a throwaway request before the timed trace so XLA
compilation is excluded (same protocol as bench_serve.py).

    PYTHONPATH=src python benchmarks/bench_spec.py --quick
"""
import argparse
import datetime
import json
import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
for _p in (str(_REPO / "src"), str(_REPO / "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from _serve_common import request_trace, warm_engine  # noqa: E402

SCHEMA_VERSION = 1


def _drain(eng, make_trace, *, warm_prompt_len, warm_max_new, reps,
           tps_key="decode_tps"):
    """Warm the engine's jit shapes with a throwaway request, then run the
    timed trace ``reps`` times (fresh metrics per rep, same compiled fns)
    and keep the rep with the median ``tps_key`` — the host this runs on is
    shared, so single-shot wall-clock throughput is noisy while the
    tick/token counts are deterministic."""
    from repro.serve import EngineMetrics
    warm_engine(eng, prompt_len=warm_prompt_len, max_new=warm_max_new)
    outs = []
    for _ in range(reps):
        eng.metrics = EngineMetrics()
        reqs = make_trace()
        for r in reqs:
            eng.submit(r)
        m = eng.run_until_drained()
        out = m.summary()
        out["outputs"] = [r.output for r in reqs]
        outs.append(out)
    # .get: a trace whose requests all finish during prefill never runs a
    # verify step, so the speculative summary fields are absent
    outs.sort(key=lambda o: o.get(tps_key, 0.0))
    return outs[len(outs) // 2]


def bench(*, arch: str, requests: int, prompt_len: int, max_new: int,
          slots: int, page_size: int, prefill_chunk: int, ks,
          with_model_draft: bool, reps: int):
    import jax

    from repro.configs import get_config, get_draft_config
    from repro.models import build_draft_model, build_model
    from repro.parallel.sharding import ParallelContext
    from repro.serve import PagedServeEngine
    from repro.spec import NgramDraft, SpeculativeServeEngine

    cfg = get_config(arch, smoke=True)
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    pctx = ParallelContext(None)
    engine_kw = dict(slots=slots, page_size=page_size,
                     prefill_chunk=prefill_chunk)
    warm = dict(warm_prompt_len=prefill_chunk + 1, warm_max_new=4, reps=reps)
    make_trace = lambda: request_trace(requests, prompt_len, max_new)  # noqa: E731

    plain = PagedServeEngine(bundle, params, pctx, **engine_kw)
    plain_out = _drain(plain, make_trace, **warm)
    reference = plain_out["outputs"]

    draft_cfg = get_draft_config(arch, smoke=True) if with_model_draft else None
    draft_bundle = draft_params = None
    if draft_cfg is not None:
        draft_bundle = build_draft_model(cfg, draft_cfg)
        draft_params = draft_bundle.init_params(jax.random.PRNGKey(1))

    engines = {"plain": plain_out}
    per_k = []
    identical = True
    configs = [("ngram", k) for k in ks]
    if draft_bundle is not None:
        # the paired draft model rides at the largest K (its per-step cost
        # is K draft forwards, so that is where pairing pays or hurts most)
        configs += [("model", max(ks))]
    for kind, k in configs:
        if kind == "ngram":
            eng = SpeculativeServeEngine(
                bundle, params, pctx, spec_k=k, draft=NgramDraft(),
                **engine_kw)
        else:
            eng = SpeculativeServeEngine(
                bundle, params, pctx, spec_k=k, draft_bundle=draft_bundle,
                draft_params=draft_params, **engine_kw)
        out = _drain(eng, make_trace, tps_key="spec_decode_tps", **warm)
        same = out["outputs"] == reference
        identical = identical and same
        name = f"spec_{kind}_k{k}"
        engines[name] = out
        per_k.append({
            "engine": name,
            "draft": kind,
            "k": k,
            "acceptance_rate": out.get("acceptance_rate", 0.0),
            "tokens_per_step": out.get("tokens_per_step", 0.0),
            "spec_decode_tps": out.get("spec_decode_tps", 0.0),
            "speedup_vs_plain": round(
                out.get("spec_decode_tps", 0.0)
                / max(plain_out["decode_tps"], 1e-9), 3),
            "outputs_identical": same,
        })

    for out in engines.values():
        out.pop("outputs")
    best = max((row["speedup_vs_plain"] for row in per_k), default=0.0)
    return {
        "schema_version": SCHEMA_VERSION,
        "generated_at": datetime.datetime.now().isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "arch": arch,
        "draft_arch": draft_cfg.name if draft_cfg is not None else None,
        "workload": {"requests": requests, "prompt_len": prompt_len,
                     "max_new": max_new, "slots": slots,
                     "page_size": page_size, "prefill_chunk": prefill_chunk,
                     "ks": list(ks), "reps": reps},
        "engines": engines,
        "per_k": per_k,
        "outputs_identical": identical,
        "best_speedup": best,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized trace (fewer/shorter requests, fewer K)")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--ks", type=int, nargs="+", default=None,
                    help="spec_k values to sweep")
    ap.add_argument("--reps", type=int, default=3,
                    help="trace repetitions per engine; the median-"
                         "throughput rep is reported (noisy shared hosts)")
    ap.add_argument("--no-model-draft", action="store_true",
                    help="skip the paired-draft-model configuration")
    ap.add_argument("--out", default=str(_REPO / "BENCH_spec.json"))
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    defaults = ((6, 16, 48, (2, 4)) if args.quick else (8, 32, 64, (2, 4, 8)))
    requests = args.requests or defaults[0]
    prompt_len = args.prompt_len or defaults[1]
    max_new = args.max_new or defaults[2]
    ks = tuple(args.ks) if args.ks else defaults[3]

    report = bench(arch=args.arch, requests=requests, prompt_len=prompt_len,
                   max_new=max_new, slots=args.slots,
                   page_size=args.page_size,
                   prefill_chunk=min(args.prefill_chunk, prompt_len),
                   ks=ks, with_model_draft=not args.no_model_draft,
                   reps=max(1, args.reps))
    Path(args.out).write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")

    print(f"wrote {args.out} (backend={report['backend']}, "
          f"outputs_identical={report['outputs_identical']})")
    print(f"  plain decode tok/s: {report['engines']['plain']['decode_tps']:.1f}")
    for row in report["per_k"]:
        print(f"  {row['engine']:>14}: acceptance={row['acceptance_rate']:.0%} "
              f"tokens/step={row['tokens_per_step']:.2f} "
              f"decode tok/s={row['spec_decode_tps']:.1f} "
              f"({row['speedup_vs_plain']:.2f}x)")
    print(f"  best speedup: {report['best_speedup']:.2f}x")
    if not report["outputs_identical"]:
        print("FAIL: speculative greedy outputs differ from the plain paged "
              "engine", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
