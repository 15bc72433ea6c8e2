"""Serving launcher: asyncio streaming server over paged continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b --requests 6

The model is built at its published widths with random weights from
``--seed``; ``--smoke`` serves the reduced same-family config instead (the
size the CPU tests and examples use).  ``chip_smoke.py`` at the repo root
drives the same functions (:func:`load_model`, :func:`build_engine`,
:func:`serve_prompts`) on the chip.

Requests are submitted through :class:`repro.serve.AsyncServeFrontend` and
stream their tokens back concurrently — the engine admits late arrivals
mid-flight instead of draining a fixed batch.  ``--slo-mix`` marks every
other request TTFT-class (priority admission with aged anti-starvation,
``docs/serving.md``); per-request rows in the report show class, TTFT,
latency, and queue-jump counts.

``--engine slot`` falls back to the contiguous slot engine (the numerics
baseline, and the only path for the audio family).  Recurrent-state
families (rwkv6 / mamba2 / zamba2) serve on the paged engine through the
state-slot pool (``repro.serve.state_cache``; ``--state-dtype int8``
stores the big state leaves int8): token-identical to the slot engine,
with ``--spec-k``/``--draft-model ngram`` speculation working through
snapshot-ring rollback.  ``--prefix-sharing`` is rejected for them with a
reason — a recurrent state is a lossy running summary, so cached prefix
KV cannot be attached mid-sequence.

Prefix cache (``--prefix-sharing``): requests whose prompts share a prefix
attach the cached KV pages read-only instead of re-prefilling them;
copy-on-write splits on divergence.  The launcher's default prompts share
a common head so the effect shows up in ``prefix hits`` / the effective-KV
multiplier line.

Multi-precision (`repro.quant`, docs/quantization.md): ``--int8-weights``
serves the int8-weight variant of the model, ``--kv-dtype int8`` stores the
paged KV cache as int8 + per-(page slot, head) scales.

Speculative decoding (`repro.spec`, docs/architecture.md): ``--draft-model``
picks the draft proposer — ``ngram`` (self-drafting), ``auto`` (the draft
arch registered for the target in ``repro.configs.DRAFT_FOR``, falling back
to ngram), or an explicit draft arch name; ``--spec-k`` sets the per-slot
proposal budget.  Greedy outputs are token-identical to the plain engine.

Tensor parallelism (`repro.parallel.tp`, docs/parallel.md): ``--mesh N``
shards attention heads, MLP blocks, and the KV page pools of the paged
engine over the first N devices; greedy outputs stay token-identical to
``--mesh 1``.  On a CPU-only machine the launcher simulates the devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) before jax loads.
"""
import argparse
import asyncio
import os
from typing import List, Optional, Sequence, Tuple


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced same-family config instead of "
                         "the published widths")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the random weights")
    ap.add_argument("--engine", choices=("paged", "slot"), default="paged")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page pool size (default: slots * 256/page_size)")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="refcounted prefix cache with copy-on-write pages "
                         "(paged engine only; docs/serving.md)")
    ap.add_argument("--slo-mix", action="store_true",
                    help="submit every other request as TTFT-class (priority "
                         "admission; the rest are throughput-class FIFO)")
    ap.add_argument("--int8-weights", action="store_true",
                    help="serve the int8-weight variant "
                         "(repro.quant.quantize_params)")
    ap.add_argument("--kv-dtype", choices=("bfloat16", "int8"),
                    default="bfloat16",
                    help="paged KV page-pool storage dtype")
    ap.add_argument("--state-dtype", choices=("float32", "int8"),
                    default="float32",
                    help="recurrent-state pool storage dtype (ssm/mamba/"
                         "hybrid on the paged engine; int8 is lossy)")
    ap.add_argument("--graph-prefill", action="store_true",
                    help="route chunked prefill through the repro.graph "
                         "fused executor (paged engine only; docs/graph.md)")
    ap.add_argument("--cost-model", choices=("on", "off"), default="on",
                    help="with --graph-prefill: choose the fusion schedule "
                         "with the repro.cost model and cache it by graph "
                         "signature ('off' reverts to the fixed pass "
                         "pipeline; docs/cost_model.md)")
    ap.add_argument("--explain", action="store_true",
                    help="print the cost model's per-pass schedule audit "
                         "for the graph-compiled steps before serving")
    ap.add_argument("--draft-model", default=None,
                    help="speculative decoding draft: 'ngram', 'auto', or a "
                         "draft arch name (repro.spec; paged engine only)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens verified per step per slot")
    ap.add_argument("--mesh", type=int, default=1,
                    help="tensor-parallel degree: shard the paged engine "
                         "over the first N devices (repro.parallel.tp; "
                         "simulated on CPU via host-platform devices)")
    return ap.parse_args(argv)


def load_model(args: argparse.Namespace):
    """``(cfg, bundle, params)`` for ``args.arch``: published widths, or
    the smoke config under ``--smoke``; random weights from ``--seed``,
    int8 under ``--int8-weights``."""
    import jax

    from ..configs import get_config
    from ..models import build_model

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke and args.mesh > 1 and (cfg.num_heads % args.mesh
                                         or cfg.num_kv_heads % args.mesh
                                         or cfg.d_ff % args.mesh):
        # Lift the reduced smoke geometry to a TP-divisible head layout
        # (published configs divide naturally; the smoke ones are tiny).
        import dataclasses
        up = lambda v, n: -(-v // n) * n
        hkv = up(cfg.num_kv_heads, args.mesh)
        h = up(max(cfg.num_heads, hkv), hkv)   # whole GQA groups per shard
        cfg = dataclasses.replace(cfg, num_heads=h, num_kv_heads=hkv,
                                  head_dim=cfg.resolved_head_dim,
                                  d_ff=up(cfg.d_ff, args.mesh))
        print(f"note: smoke geometry lifted for --mesh {args.mesh}: "
              f"heads={h} kv_heads={hkv} d_ff={cfg.d_ff}")
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(args.seed))
    if args.int8_weights:
        params = bundle.quantize_params(params)
    return cfg, bundle, params


def build_engine(args: argparse.Namespace, cfg, bundle, params):
    """The serving engine the flags describe, over ``params``."""
    import jax

    from ..configs import get_draft_config
    from ..parallel.sharding import ParallelContext
    from ..serve import PagedServeEngine, ServeEngine

    if args.mesh > 1:
        if not (args.engine == "paged" and bundle.supports_paged_kv):
            raise SystemExit(
                f"--mesh requires the paged engine and a paged-KV family "
                f"(got --engine {args.engine}, family {cfg.family!r})")
        if args.graph_prefill:
            raise SystemExit("--graph-prefill is incompatible with --mesh "
                             "(the graph executor is a host-side op loop)")
        from ..parallel.tp import make_serving_mesh, make_tp_context
        pctx = make_tp_context(make_serving_mesh(args.mesh))
        print(f"mesh: {args.mesh}-way tensor parallel over "
              f"{[str(d) for d in pctx.mesh.devices.flat]}")
    else:
        pctx = ParallelContext(None)
    if args.draft_model and not (args.engine == "paged"
                                 and bundle.supports_paged_serving):
        raise SystemExit(f"--draft-model requires the paged engine and a "
                         f"paged-serving family (got --engine {args.engine},"
                         f" family {cfg.family!r})")
    if args.graph_prefill and cfg.family == "hybrid":
        raise SystemExit(
            "--graph-prefill is unsupported for the hybrid family: the "
            "graph executor's cluster boundaries make the f32 SSD update "
            "FMA-contraction sensitive, so token identity to the jit path "
            "cannot be guaranteed (run without --graph-prefill)")
    if args.prefix_sharing and bundle.supports_paged_state:
        raise SystemExit(
            f"--prefix-sharing is unsupported for the {cfg.family!r} "
            "family: a recurrent state is a lossy running summary of its "
            "whole history, so cached prefix KV cannot be attached "
            "mid-sequence (run without --prefix-sharing)")
    if args.engine == "paged" and bundle.supports_paged_serving:
        engine_kw = dict(slots=args.slots, page_size=args.page_size,
                         num_pages=args.num_pages,
                         prefill_chunk=args.prefill_chunk,
                         kv_dtype=args.kv_dtype,
                         state_dtype=args.state_dtype,
                         prefix_sharing=args.prefix_sharing,
                         use_graph=args.graph_prefill,
                         graph_cost_model=(args.cost_model == "on"))
        if args.draft_model:
            from ..models import build_draft_model
            from ..spec import SpeculativeServeEngine

            if args.draft_model == "ngram":
                draft_cfg = None
            elif args.draft_model == "auto":
                draft_cfg = get_draft_config(args.arch, smoke=args.smoke)
            else:  # an explicit *draft* arch name (not a target arch)
                draft_cfg = get_draft_config(args.draft_model,
                                             smoke=args.smoke, pairing=False)
                if draft_cfg is None:
                    raise SystemExit(
                        f"no draft config registered as {args.draft_model!r}")
            if draft_cfg is None:
                print(f"speculative: ngram self-draft, k={args.spec_k}")
                engine = SpeculativeServeEngine(
                    bundle, params, pctx, spec_k=args.spec_k, **engine_kw)
            else:
                print(f"speculative: draft={draft_cfg.name}, k={args.spec_k}")
                draft_bundle = build_draft_model(cfg, draft_cfg)
                draft_params = draft_bundle.init_params(
                    jax.random.PRNGKey(args.seed + 1))
                engine = SpeculativeServeEngine(
                    bundle, params, pctx, spec_k=args.spec_k,
                    draft_bundle=draft_bundle, draft_params=draft_params,
                    **engine_kw)
        else:
            engine = PagedServeEngine(bundle, params, pctx, **engine_kw)
    else:
        if args.engine == "paged":
            print(f"note: {cfg.family!r} family has no paged KV cache or "
                  "state pool; using the contiguous slot engine")
        if args.kv_dtype != "bfloat16":
            print(f"note: --kv-dtype {args.kv_dtype} only applies to the "
                  "paged engine; the slot engine keeps its bf16 cache")
        if args.prefix_sharing:
            print("note: --prefix-sharing only applies to the paged engine")
        engine = ServeEngine(bundle, params, pctx, slots=args.slots,
                             max_seq=max(128, args.prompt_len + args.max_new + 2))
    return engine


def launcher_prompts(n: int, prompt_len: int) -> List[List[int]]:
    """A shared prompt head (the "system prompt") + a per-request tail, so
    --prefix-sharing has something to dedupe."""
    head_len = max(prompt_len // 2, 1)
    head = list(range(2, 2 + head_len))
    return [head + [100 + i] * (prompt_len - head_len) for i in range(n)]


def serve_prompts(engine, prompts: Sequence[List[int]], max_new: int, *,
                  slo_mix: bool = False) -> Tuple[List[List[int]], list]:
    """Submit every prompt through :class:`AsyncServeFrontend` at once and
    drain the streams; returns (tokens per request, per-request metrics).
    ``slo_mix`` marks every other request TTFT-class."""
    from ..serve import SLO_THROUGHPUT, SLO_TTFT, AsyncServeFrontend

    async def serve():
        async with AsyncServeFrontend(engine) as front:
            streams = []
            for i, prompt in enumerate(prompts):
                slo = SLO_TTFT if slo_mix and i % 2 else SLO_THROUGHPUT
                streams.append(await front.submit(
                    prompt, max_new_tokens=max_new, slo=slo))
            outs = await asyncio.gather(*(s.drain() for s in streams))
            return list(outs), [s.metrics() for s in streams]

    return asyncio.run(serve())


def print_report(args: argparse.Namespace, engine, rows: list) -> None:
    from ..serve import PagedServeEngine

    done = sum(1 for row in rows if row["tokens"] > 0)
    print(f"served {done}/{args.requests} requests")
    for row in rows:
        ttft = f"{row['ttft_s'] * 1e3:.1f}ms" if row["ttft_s"] else "-"
        lat = f"{row['latency_s'] * 1e3:.1f}ms" if row["latency_s"] else "-"
        print(f"  r{row['rid']:<3} slo={row['slo']:<10} "
              f"tokens={row['tokens']:<4} ttft={ttft:<9} latency={lat:<9} "
              f"preempt={row['preemptions']} jumped={row['queue_jumped']}")
    if isinstance(engine, PagedServeEngine):
        m = engine.metrics
        print(f"  ticks={m.ticks}  prefill={m.prefill_tokens} tok "
              f"({m.prefill_tps:.1f} tok/s)  decode={m.decode_tokens} tok "
              f"({m.decode_tps:.1f} tok/s)")
        if m.ttfts:
            print(f"  ttft mean={m.mean_ttft * 1e3:.1f}ms "
                  f"p50={m.p50_ttft * 1e3:.1f}ms")
        print(f"  page utilization peak={m.peak_page_utilization:.0%} "
              f"mean={m.mean_page_utilization:.0%}  "
              f"preemptions={m.preemptions}")
        if m.prefix_hit_requests or m.cow_copies:
            print(f"  prefix cache: hits={m.prefix_hit_requests} req / "
                  f"{m.prefix_hit_tokens} tok  cow={m.cow_copies}  "
                  f"effective-KV x{m.effective_kv_multiplier:.2f} "
                  f"({m.prompt_pages_logical} logical / "
                  f"{m.prompt_pages_unique} unique pages)")
        if m.spec_steps:
            print(f"  speculative: acceptance={m.acceptance_rate:.0%}  "
                  f"tokens/step={m.tokens_per_step:.2f}  "
                  f"decode tok/s incl draft={m.spec_decode_tps:.1f}")
        if engine.tp_plan is not None:
            print(f"  tensor parallel: {engine.tp_plan.degree} shards  "
                  f"kv pool/device={engine.kv_pool_bytes_per_device()}B "
                  f"(logical {engine.kv_pool_bytes()}B)  "
                  f"weights/device={engine.weight_bytes_per_device()}B")


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    if args.mesh > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # must be decided before jax initialises its backends; real
        # accelerators already expose their device count, but a plain CPU
        # process defaults to one device
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.mesh}").strip()
    from .compile_cache import use_compile_cache
    use_compile_cache()

    cfg, bundle, params = load_model(args)
    engine = build_engine(args, cfg, bundle, params)
    if args.explain:
        from ..serve import PagedServeEngine
        report = (engine.graph_schedule_report()
                  if isinstance(engine, PagedServeEngine) else "")
        print(report if report else
              "no graph schedules to explain (needs --graph-prefill with "
              "the cost model on)")
    _, rows = serve_prompts(engine,
                            launcher_prompts(args.requests, args.prompt_len),
                            args.max_new, slo_mix=args.slo_mix)
    print_report(args, engine, rows)


if __name__ == "__main__":
    main()
