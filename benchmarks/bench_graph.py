"""Graph-compiler benchmark: fused vs unfused execution + traffic plan.

Three sections, one JSON report (``BENCH_graph.json``, schema in
benchmarks/README.md):

* ``cnn``     — the paper's CNNs lowered by ``repro.graph.trace`` and run
  through the executor fused vs unfused: us/forward, node/cluster counts,
  planner intermediate-HBM-bytes before/after fusion, arena-reuse factor,
  and max-abs-err of both paths against the direct XLA forward,
* ``prefill`` — the smoke LM's chunked-prefill step (the paged serve
  contract at B=1, T=chunk) graph-compiled fused vs unfused: us/chunk and
  the same planner numbers.  This is the headline fused-vs-unfused
  latency the CI gate checks (>= 1.2x),
* ``decode``  — the batched T=1 decode tick graph-compiled fused vs
  unfused for the attention LM **and** one recurrent family (rwkv6,
  state gather/scatter through the fused clusters): intermediate-HBM
  bytes must drop for both (gated; no latency gate — a single tick is
  dispatch-dominated off-TPU).  The hybrid family is excluded by design:
  the engine rejects ``use_graph`` for it (FMA-contraction sensitivity
  at cluster boundaries),
* ``engine``  — the same request trace through ``PagedServeEngine`` with
  ``use_graph=False`` vs ``use_graph=True``: **greedy outputs must be
  token-identical** (gated) plus prefill/decode tok/s for context.
  ``engine_recurrent`` repeats the comparison on the rwkv6 engine, whose
  graph path compiles the decode tick too (same identity gate).

Unfused execution runs every primitive as its own compiled call — every
intermediate materializes, the graph-level HBM baseline.  Fused execution
runs the fusion-pass clusters as single compiled regions (the graph-level
APR).  Off-TPU both paths execute through XLA-CPU, so times are a
dispatch/materialization-boundary proxy (the ``backend`` field records
this); planner byte counts are analytic and backend-independent.

    PYTHONPATH=src python benchmarks/bench_graph.py --quick
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

from _serve_common import request_trace as _trace  # noqa: E402
from _serve_common import warm_engine  # noqa: E402

SCHEMA_VERSION = 1
GATE_SPEEDUP = 1.2

#: the recurrent family the decode-tick section runs next to the
#: attention LM (the hybrid is excluded — the engine rejects use_graph
#: for it; see repro.graph.compiler.compile_decode_step)
RECURRENT_ARCH = "rwkv6-3b"


def _graph_stats(graph):
    from repro.graph import arena_plan, memory_report
    mem = memory_report(graph)
    arena = arena_plan(graph)
    s = graph.summary()
    return {
        "n_nodes": s["n_nodes"],
        "n_fused_clusters": s["n_fused"],
        "n_primitive_ops": s["n_primitive_ops"],
        "intermediate_hbm_bytes": mem.intermediate_bytes,
        "intermediate_hbm_traffic": mem.intermediate_traffic,
        "arena_bytes": arena.arena_bytes,
        "arena_naive_bytes": arena.naive_bytes,
        "arena_reuse_factor": round(arena.reuse_factor, 3),
    }


def bench_cnn(names, iters: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.bench.autotune import time_callable
    from repro.graph import GraphExecutor, run_passes, trace
    from repro.models.cnn import CNNS

    out = {}
    for name in names:
        spec = CNNS[name]
        params = spec["params"](jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2,) + spec["input"])
        fwd = lambda xx: spec["forward"](params, xx)
        ref = np.asarray(fwd(x))
        ex_u = GraphExecutor(trace(fwd, x, name=name))
        ex_f = GraphExecutor(run_passes(trace(fwd, x, name=name)))
        err_u = float(np.max(np.abs(np.asarray(ex_u(x)) - ref)))
        err_f = float(np.max(np.abs(np.asarray(ex_f(x)) - ref)))
        t_u = time_callable(lambda: ex_u(x), iters=iters)
        t_f = time_callable(lambda: ex_f(x), iters=iters)
        su, sf = _graph_stats(ex_u.graph), _graph_stats(ex_f.graph)
        out[name] = {
            "us_unfused": round(t_u * 1e6, 1),
            "us_fused": round(t_f * 1e6, 1),
            "fused_speedup": round(t_u / t_f, 3),
            "max_abs_err_unfused": round(err_u, 6),
            "max_abs_err_fused": round(err_f, 6),
            "unfused": su,
            "fused": sf,
            "intermediate_bytes_reduction": round(
                su["intermediate_hbm_bytes"]
                / max(sf["intermediate_hbm_bytes"], 1), 3),
        }
    return out


def bench_prefill(bundle, params, pctx, *, chunk: int, page_size: int,
                  iters: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.bench.autotune import time_callable
    from repro.graph.compiler import compile_prefill_step

    width = max(256 // page_size, 1)          # engine-default table width
    pool_pages = 2 * width + 1
    cache = bundle.init_paged_cache(pool_pages, page_size)
    build = lambda fused: compile_prefill_step(
        bundle, params, cache, chunk=chunk, table_width=width, pctx=pctx,
        fused=fused)
    fused, unfused = build(True), build(False)
    toks = jnp.ones((1, chunk), jnp.int32)
    lengths = jnp.zeros((1,), jnp.int32)
    counts = jnp.full((1,), chunk, jnp.int32)
    bt = jnp.arange(1, width + 1, dtype=jnp.int32)[None]
    args = (params, cache, toks, lengths, counts, bt)
    lf = np.asarray(fused(*args)[0], np.float32)
    lu = np.asarray(unfused(*args)[0], np.float32)
    # this section carries the CI gate: extra reps + warmup so a single
    # scheduler hiccup on a shared runner can't flip the >= 1.2x check
    gate_iters = max(iters, 5)
    t_f = time_callable(lambda: fused(*args)[0], iters=gate_iters, warmup=2)
    t_u = time_callable(lambda: unfused(*args)[0], iters=gate_iters, warmup=2)
    su = _graph_stats(unfused.executor.graph)
    sf = _graph_stats(fused.executor.graph)
    return {
        "chunk": chunk,
        "us_unfused": round(t_u * 1e6, 1),
        "us_fused": round(t_f * 1e6, 1),
        "fused_speedup": round(t_u / t_f, 3),
        "logits_max_abs_err": round(float(np.max(np.abs(lf - lu))), 6),
        "unfused": su,
        "fused": sf,
        "intermediate_bytes_reduction": round(
            su["intermediate_hbm_bytes"]
            / max(sf["intermediate_hbm_bytes"], 1), 3),
    }


def bench_decode(arch, pctx, *, slots: int, page_size: int, iters: int,
                 bundle, params):
    """The batched T=1 decode tick graph-compiled fused vs unfused — the
    serve-loop sibling of :func:`bench_prefill`, at the engine's decode
    geometry (B=slots).  State families get the combined block table (KV
    page columns + state read col + one write col) and a state pool."""
    import jax.numpy as jnp
    import numpy as np

    from repro.bench.autotune import time_callable
    from repro.graph.compiler import compile_decode_step
    from repro.serve.state_cache import StateCache

    width = max(256 // page_size, 1)          # engine-default table width
    state = StateCache(slots=slots) if bundle.supports_paged_state else None
    table_width = width + (2 if state else 0)  # + read col + T=1 write col
    cache = bundle.init_paged_cache(
        slots + 2, page_size,
        state_slots=(state.pool_slots if state else 0))
    build = lambda fused: compile_decode_step(
        bundle, params, cache, slots=slots, table_width=table_width,
        pctx=pctx, fused=fused)
    fused, unfused = build(True), build(False)
    # one mid-page token per slot: page i+1, position page_size // 2
    toks = jnp.ones((slots, 1), jnp.int32)
    lengths = jnp.full((slots,), page_size // 2, jnp.int32)
    counts = jnp.ones((slots,), jnp.int32)
    kv = np.zeros((slots, width), np.int32)
    kv[:, 0] = 1 + np.arange(slots)
    if state is not None:
        ids = np.array([[state.alloc(s)] for s in range(slots)], np.int32)
        bt = jnp.asarray(np.concatenate([kv, ids, ids], axis=1))
    else:
        bt = jnp.asarray(kv)
    args = (params, cache, toks, lengths, counts, bt)
    lf = np.asarray(fused(*args)[0], np.float32)
    lu = np.asarray(unfused(*args)[0], np.float32)
    t_f = time_callable(lambda: fused(*args)[0], iters=iters, warmup=1)
    t_u = time_callable(lambda: unfused(*args)[0], iters=iters, warmup=1)
    su = _graph_stats(unfused.executor.graph)
    sf = _graph_stats(fused.executor.graph)
    return {
        "slots": slots,
        "us_unfused": round(t_u * 1e6, 1),
        "us_fused": round(t_f * 1e6, 1),
        "fused_speedup": round(t_u / t_f, 3),
        "logits_max_abs_err": round(float(np.max(np.abs(lf - lu))), 6),
        "unfused": su,
        "fused": sf,
        "intermediate_bytes_reduction": round(
            su["intermediate_hbm_bytes"]
            / max(sf["intermediate_hbm_bytes"], 1), 3),
    }


def _run_engine(bundle, params, pctx, reqs, *, slots, page_size,
                prefill_chunk, use_graph):
    from repro.serve import PagedServeEngine
    eng = PagedServeEngine(bundle, params, pctx, slots=slots,
                           page_size=page_size, prefill_chunk=prefill_chunk,
                           use_graph=use_graph)
    warm_engine(eng, prompt_len=prefill_chunk + 1)
    for r in reqs:
        eng.submit(r)
    m = eng.run_until_drained()
    out = {k: m.summary()[k] for k in
           ("requests_done", "prefill_tokens", "decode_tokens",
            "prefill_tps", "decode_tps")}
    return out, [r.output for r in reqs]


def bench(*, arch: str, quick: bool, requests: int, prompt_len: int,
          max_new: int, slots: int, page_size: int, prefill_chunk: int,
          iters: int):
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.parallel.sharding import ParallelContext

    cfg = get_config(arch, smoke=True)
    if cfg.family not in ("dense", "moe", "vlm"):
        raise SystemExit(
            f"bench_graph needs a dense/moe/vlm arch (paged prefill is the "
            f"graph-compiled step); {arch!r} is family {cfg.family!r}")
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    pctx = ParallelContext(None)

    cnn_names = ["lenet"] if quick else ["lenet", "resnet20"]
    run = lambda g: _run_engine(
        bundle, params, pctx, _trace(requests, prompt_len, max_new),
        slots=slots, page_size=page_size, prefill_chunk=prefill_chunk,
        use_graph=g)
    eng_plain, out_plain = run(False)
    eng_graph, out_graph = run(True)

    # T=1 decode tick: the attention LM (this bench's arch) plus one
    # recurrent family — rwkv6, whose graph decode runs the state
    # gather/scatter through the fused clusters.  The hybrid family is
    # deliberately absent: PagedServeEngine rejects use_graph for it
    # (FMA-contraction sensitivity at cluster boundaries; see
    # repro.graph.compiler.compile_decode_step).
    r_bundle = build_model(get_config(RECURRENT_ARCH, smoke=True))
    r_params = r_bundle.init_params(jax.random.PRNGKey(0))
    decode = {
        arch: bench_decode(arch, pctx, slots=slots, page_size=page_size,
                           iters=iters, bundle=bundle, params=params),
        RECURRENT_ARCH: bench_decode(
            RECURRENT_ARCH, pctx, slots=slots, page_size=page_size,
            iters=iters, bundle=r_bundle, params=r_params)}
    run_r = lambda g: _run_engine(
        r_bundle, r_params, pctx, _trace(requests, prompt_len, max_new),
        slots=slots, page_size=page_size, prefill_chunk=prefill_chunk,
        use_graph=g)
    reng_plain, rout_plain = run_r(False)
    reng_graph, rout_graph = run_r(True)

    return {
        "schema_version": SCHEMA_VERSION,
        "generated_at": datetime.datetime.now().isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "mode": "quick" if quick else "full",
        "arch": arch,
        "workload": {"requests": requests, "prompt_len": prompt_len,
                     "max_new": max_new, "slots": slots,
                     "page_size": page_size, "prefill_chunk": prefill_chunk},
        "cnn": bench_cnn(cnn_names, iters),
        "prefill": bench_prefill(bundle, params, pctx, chunk=prefill_chunk,
                                 page_size=page_size, iters=iters),
        "decode": decode,
        "engine": {"jit": eng_plain, "graph": eng_graph},
        "engine_recurrent": {"arch": RECURRENT_ARCH, "jit": reng_plain,
                             "graph": reng_graph},
        "tokens_identical_graph_engine": out_plain == out_graph,
        "tokens_identical_graph_engine_recurrent": rout_plain == rout_graph,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized: LeNet only + small trace")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=str(_REPO / "BENCH_graph.json"))
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    defaults = ((3, 24, 6) if args.quick else (6, 48, 12))
    requests = args.requests or defaults[0]
    prompt_len = args.prompt_len or defaults[1]
    max_new = args.max_new or defaults[2]

    report = bench(arch=args.arch, quick=args.quick, requests=requests,
                   prompt_len=prompt_len, max_new=max_new, slots=args.slots,
                   page_size=args.page_size,
                   prefill_chunk=min(args.prefill_chunk, prompt_len),
                   iters=args.iters)
    Path(args.out).write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")

    p = report["prefill"]
    print(f"wrote {args.out} (backend={report['backend']}, "
          f"arch={report['arch']})")
    print(f"  prefill chunk (T={p['chunk']}): fused {p['us_fused']}us vs "
          f"unfused {p['us_unfused']}us -> {p['fused_speedup']:.2f}x; "
          f"intermediate HBM bytes {p['unfused']['intermediate_hbm_bytes']}"
          f" -> {p['fused']['intermediate_hbm_bytes']} "
          f"({p['intermediate_bytes_reduction']:.2f}x)")
    for name, c in report["cnn"].items():
        print(f"  {name}: fused {c['us_fused']}us vs unfused "
              f"{c['us_unfused']}us -> {c['fused_speedup']:.2f}x; "
              f"bytes {c['intermediate_bytes_reduction']:.2f}x; "
              f"arena reuse {c['unfused']['arena_reuse_factor']:.2f}x")
    for name, d in report["decode"].items():
        print(f"  decode tick ({name}, B={d['slots']}): fused "
              f"{d['us_fused']}us vs unfused {d['us_unfused']}us -> "
              f"{d['fused_speedup']:.2f}x; intermediate HBM bytes "
              f"{d['unfused']['intermediate_hbm_bytes']} -> "
              f"{d['fused']['intermediate_hbm_bytes']} "
              f"({d['intermediate_bytes_reduction']:.2f}x)")
    print(f"  graph-engine greedy tokens identical: "
          f"{report['tokens_identical_graph_engine']} (attention), "
          f"{report['tokens_identical_graph_engine_recurrent']} "
          f"({report['engine_recurrent']['arch']})")
    ok = (report["tokens_identical_graph_engine"]
          and report["tokens_identical_graph_engine_recurrent"]
          and p["fused_speedup"] >= GATE_SPEEDUP
          and p["intermediate_bytes_reduction"] > 1.0
          and all(d["intermediate_bytes_reduction"] > 1.0
                  for d in report["decode"].values()))
    if not ok:
        print(f"FAIL: graph prefill must be >= {GATE_SPEEDUP}x faster fused "
              "than unfused, fusion must cut intermediate HBM bytes on the "
              "prefill chunk and every decode tick, and both graph engines "
              "(attention + recurrent) must emit identical greedy tokens",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
