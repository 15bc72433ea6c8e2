"""Quantized-inference benchmark: int8 weights / int8 KV vs the float path.

Three sections, one JSON report (``BENCH_quant.json``, schema in
benchmarks/README.md):

* ``kernel``  — ``quant_matmul`` (int8 x int8, int32 APR) vs ``apr_matmul``
  (fp32 APR) on the same GEMM: us/call, analytic weight bytes streamed, and
  max-abs-err of the quantized result against the fp32 product,
* ``weights`` — byte accounting for the smoke model's int8-weight variant
  (``repro.quant.quantize_params``): fp32 / bf16 / int8+scales footprints of
  the streamed matmul weights — the bytes a decode step moves per token,
* ``engine``  — the same request trace through ``PagedServeEngine`` with
  (a) float weights, (b) int8 weights, (c) int8 weights + int8 paged KV:
  decode/prefill tok/s, KV pool bytes, **greedy top-1 token identity**
  against the float path, and max-abs-err of the int8-weight logits.

Off-TPU everything runs in Pallas-interpret / XLA-CPU mode, so times are a
correctness-path proxy (the ``backend`` field records this); byte counts
are analytic and backend-independent.

    PYTHONPATH=src python benchmarks/bench_quant.py --quick
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

SHAPES = {"quick": {"m": 64, "k": 128, "n": 64},
          "full": {"m": 256, "k": 2048, "n": 512}}


def bench_kernel(shape, iters: int):
    import jax
    import jax.numpy as jnp

    from repro.bench.autotune import time_callable
    from repro.kernels.apr_matmul import ops as fp_ops
    from repro.kernels.quant_matmul import ops as q_ops
    from repro.kernels.quant_matmul.ref import quant_matmul_ref

    m, k, n = shape["m"], shape["k"], shape["n"]
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(ky, (k, n), jnp.float32)
    w_q, w_scale = q_ops.quantize_weights(w)

    fp = fp_ops.apr_matmul(x, w)
    qt = q_ops.quant_matmul(x, w_q, w_scale)
    err_fp = float(jnp.max(jnp.abs(qt - fp)))
    err_oracle = float(jnp.max(jnp.abs(qt - quant_matmul_ref(x, w_q, w_scale))))
    t_fp = time_callable(lambda: fp_ops.apr_matmul(x, w), iters=iters)
    t_q = time_callable(lambda: q_ops.quant_matmul(x, w_q, w_scale),
                        iters=iters)
    w_bytes_fp32 = k * n * 4
    w_bytes_int8 = k * n * 1 + n * 4          # payload + per-channel scales
    return {
        "shape": dict(shape),
        "us_apr_matmul_fp32": round(t_fp * 1e6, 2),
        "us_quant_matmul_int8": round(t_q * 1e6, 2),
        "weight_bytes_fp32": w_bytes_fp32,
        "weight_bytes_int8": w_bytes_int8,
        "weight_bytes_reduction": round(w_bytes_fp32 / w_bytes_int8, 3),
        "max_abs_err_vs_fp32": round(err_fp, 6),
        "max_abs_err_vs_oracle": round(err_oracle, 9),
    }


def _run_engine(bundle, params, pctx, reqs, *, slots, page_size,
                prefill_chunk, kv_dtype):
    from repro.serve import PagedServeEngine
    eng = PagedServeEngine(bundle, params, pctx, slots=slots,
                           page_size=page_size, prefill_chunk=prefill_chunk,
                           kv_dtype=kv_dtype)
    # warm the jit caches so the timed trace measures steady-state serving
    warm_engine(eng, prompt_len=prefill_chunk + 1)
    for r in reqs:
        eng.submit(r)
    m = eng.run_until_drained()
    out = {k: m.summary()[k] for k in
           ("requests_done", "prefill_tokens", "decode_tokens",
            "prefill_tps", "decode_tps")}
    out["kv_pool_bytes"] = eng.kv_pool_bytes()
    return out, [r.output for r in reqs]


def bench(*, arch: str, quick: bool, requests: int, prompt_len: int,
          max_new: int, slots: int, page_size: int, prefill_chunk: int,
          iters: int):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model, lm
    from repro.parallel.sharding import ParallelContext
    from repro.quant import weight_bytes
    from repro.serve.paged_cache import kv_token_bytes

    cfg = get_config(arch, smoke=True)
    if cfg.family not in ("dense", "moe", "vlm"):
        # the engine section needs a paged KV cache and the logits section
        # drives lm_forward directly; audio has int8 weights but neither.
        raise SystemExit(
            f"bench_quant needs a dense/moe/vlm arch (paged-KV + lm "
            f"forward); {arch!r} is family {cfg.family!r}")
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    qparams = bundle.quantize_params(params)
    pctx = ParallelContext(None)

    # -- weights: the decode-step bandwidth story -------------------------
    wb = weight_bytes(qparams)
    weights = {
        "n_quantized_tensors": wb["n_quantized"],
        "n_passthrough_tensors": wb["n_passthrough"],
        "streamed_bytes_fp32": wb["bytes_fp32"],
        "streamed_bytes_bf16": wb["bytes_bf16"],
        "streamed_bytes_int8": wb["bytes_actual"],
        "reduction_vs_fp32": round(wb["bytes_fp32"] / wb["bytes_actual"], 3),
        "reduction_vs_bf16": round(wb["bytes_bf16"] / wb["bytes_actual"], 3),
        "kv_bytes_per_token_bf16": kv_token_bytes(
            cfg.num_kv_heads, cfg.resolved_head_dim, "bfloat16"),
        "kv_bytes_per_token_int8": kv_token_bytes(
            cfg.num_kv_heads, cfg.resolved_head_dim, "int8"),
    }

    # -- logits error (teacher-forced forward, float vs int8 weights) -----
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, prompt_len),
                              0, cfg.vocab_size)
    lf = lm.lm_forward(params, cfg, pctx, toks)
    lq = lm.lm_forward(qparams, cfg, pctx, toks)
    logits_err = float(jnp.max(jnp.abs(lf.astype(jnp.float32)
                                       - lq.astype(jnp.float32))))

    # -- engine: same trace, three precision configurations ---------------
    run = lambda ps, kv: _run_engine(
        bundle, ps, pctx, _trace(requests, prompt_len, max_new),
        slots=slots, page_size=page_size, prefill_chunk=prefill_chunk,
        kv_dtype=kv)
    eng_fp, out_fp = run(params, "bfloat16")
    eng_q, out_q = run(qparams, "bfloat16")
    eng_qkv, out_qkv = run(qparams, "int8")

    return {
        "schema_version": SCHEMA_VERSION,
        "generated_at": datetime.datetime.now().isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "mode": "quick" if quick else "full",
        "arch": arch,
        "workload": {"requests": requests, "prompt_len": prompt_len,
                     "max_new": max_new, "slots": slots,
                     "page_size": page_size, "prefill_chunk": prefill_chunk},
        "kernel": bench_kernel(SHAPES["quick" if quick else "full"], iters),
        "weights": weights,
        "logits_max_abs_err": round(logits_err, 6),
        "engine": {"float": eng_fp, "int8_weights": eng_q,
                   "int8_weights_int8_kv": eng_qkv},
        "tokens_identical_int8_weights": out_fp == out_q,
        "tokens_identical_int8_weights_int8_kv": out_fp == out_qkv,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized trace + small GEMM")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=str(_REPO / "BENCH_quant.json"))
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    defaults = ((4, 24, 8) if args.quick else (8, 64, 16))
    requests = args.requests or defaults[0]
    prompt_len = args.prompt_len or defaults[1]
    max_new = args.max_new or defaults[2]

    report = bench(arch=args.arch, quick=args.quick, requests=requests,
                   prompt_len=prompt_len, max_new=max_new, slots=args.slots,
                   page_size=args.page_size,
                   prefill_chunk=min(args.prefill_chunk, prompt_len),
                   iters=args.iters)
    Path(args.out).write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")

    w, k = report["weights"], report["kernel"]
    print(f"wrote {args.out} (backend={report['backend']}, "
          f"arch={report['arch']})")
    print(f"  weight bytes/decode step: fp32={w['streamed_bytes_fp32']}  "
          f"int8={w['streamed_bytes_int8']}  "
          f"({w['reduction_vs_fp32']:.2f}x vs fp32, "
          f"{w['reduction_vs_bf16']:.2f}x vs bf16)")
    print(f"  quant_matmul: {k['us_quant_matmul_int8']}us vs apr_matmul "
          f"{k['us_apr_matmul_fp32']}us; max|err| vs fp32 "
          f"{k['max_abs_err_vs_fp32']}")
    print(f"  logits max|err| (int8 weights): {report['logits_max_abs_err']}")
    print(f"  greedy tokens identical: int8-weights="
          f"{report['tokens_identical_int8_weights']}  +int8-kv="
          f"{report['tokens_identical_int8_weights_int8_kv']}")
    ok = (report["tokens_identical_int8_weights"]
          and report["weights"]["reduction_vs_fp32"] >= 2.0)
    if not ok:
        print("FAIL: int8-weight path must emit identical greedy tokens and "
              "move >= 2x fewer weight bytes than fp32", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
