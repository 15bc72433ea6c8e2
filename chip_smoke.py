#!/usr/bin/env python3
"""Bring-up smoke run on TPU: serve published-width models through the
normal entry points and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --mesh 4    # four chips: the tensor-parallel path

One chip: chatglm3-6b, then zamba2-1.2b (hybrid: KV pages plus recurrent
state slots), each at its published config with random bf16 weights from
``--seed``, on the paged engine with the launcher's default options (4
slots, 16-token pages).  Per model:

1. correctness: one request is served through the engine's own jitted
   ``decode_paged`` steps (chunked prefill, then decode ticks) and its
   logits rows are compared with ``bundle.forward`` over the same tokens
   (``repro.serve.logits_check``); zamba2-1.2b runs this phase on a float32
   copy of its weights (see ``TOLERANCE``);
2. serving: 8 requests with 64-200-token prompts, 32 new tokens each, go
   through ``AsyncServeFrontend`` exactly as ``repro.launch.serve`` sends
   them; every request must return all its tokens.

``--mesh 4`` serves chatglm3-6b on a 4-way tensor-parallel mesh and
compares it with its own one-chip run in this process: the same greedy
tokens for every request, and logits within the one-chip tolerance.

Every phase runs in this one process.  The script fails (exit code 1, no
result line) unless JAX's first device is a TPU.  The last line of its
output is ``{"ok": true, "device": {...}}`` and nothing else.
"""
import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
MODELS = ("chatglm3-6b", "zamba2-1.2b")
N_REQUESTS = 8
MAX_NEW = 32
PROMPT_LENS = (64, 200)
CHECK_PROMPT_LEN = 96
CHECK_STEPS = 8

#: max |served logits - forward logits| / max |forward logits| allowed.
#: A wrong page, position or state moves the logits by about their own
#: range (a ratio near 1 or more: shifting the reference by one token gives
#: 1.1-1.4 at smoke size); rounding moves them by less.
#: * chatglm3-6b, bf16 weights: the same bf16 operations, chunked and paged
#:   vs one causal pass (bitwise equal on the CPU at smoke size).  A few bf16
#:   ulps (2^-8) of difference per layer over 28 layers stays well under 5%
#:   of the range.
#: * zamba2-1.2b is checked in float32 (weights cast, matmuls at "highest"
#:   precision), because with bf16 weights the check cannot tell rounding
#:   from a bug at its published depth: random weights through 38 Mamba2
#:   layers amplify bf16 rounding to the whole logit range (on the CPU at
#:   38 layers the bf16 forward differs from the float32 forward by 1.05-1.16
#:   of the range; on the chip the bf16 engine differed from the bf16 forward
#:   by 0.84).  In float32 the same 38-layer CPU model's engine and forward
#:   differ by 0.055 of the range (the chunked SSD and the per-token
#:   recurrence round differently), so 0.25 passes amplified float32
#:   rounding and fails a wrong cache.
TOLERANCE = {"chatglm3-6b": 0.05, "zamba2-1.2b": 0.25}
FLOAT32_CHECK = {"zamba2-1.2b"}


class CompileMeter:
    """Seconds JAX spends compiling (persistent-cache reads included) and
    persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def prompts_for(vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    prompts = [rng.integers(2, vocab, n).tolist() for n in lens]
    check = rng.integers(2, vocab, CHECK_PROMPT_LEN).tolist()
    return prompts, check


def served_check(engine, bundle, params, cfg, check_prompt):
    """Logits of the engine's steps vs the plain forward; fail outside
    the model's tolerance."""
    from repro.serve.logits_check import (forward_logits, logits_error,
                                          served_logits)

    tokens, got = served_logits(engine, check_prompt, CHECK_STEPS)
    ref = forward_logits(bundle, params, check_prompt, tokens)
    abs_err, rel_err = logits_error(got, ref, cfg.vocab_size)
    tol = TOLERANCE[cfg.name]
    print(f"{cfg.name}: logits served vs forward over {CHECK_STEPS} steps: "
          f"max_abs_err={abs_err} max_rel_err={rel_err} tolerance={tol}",
          flush=True)
    if not rel_err <= tol:
        sys.exit(f"chip_smoke: {cfg.name} logits differ from the forward "
                 f"pass by {rel_err} of their range (tolerance {tol})")
    return tokens, got, abs_err, rel_err


def serve_all(engine, cfg, prompts):
    """Serve every prompt through the launcher's front end; fail unless
    each returns all its tokens, in the vocabulary."""
    from repro.launch import serve

    t0 = time.perf_counter()
    outs, _ = serve.serve_prompts(engine, prompts, MAX_NEW)
    seconds = time.perf_counter() - t0
    for i, out in enumerate(outs):
        if len(out) != MAX_NEW or not all(0 <= t < cfg.vocab_size
                                          for t in out):
            sys.exit(f"chip_smoke: {cfg.name} request {i} returned "
                     f"{len(out)}/{MAX_NEW} tokens: {out}")
    return outs, seconds


def run_model(arch: str, seed: int, meter: CompileMeter) -> None:
    import jax

    from repro.launch import serve

    compile0, hits0 = meter.seconds, meter.cache_hits
    args = serve.parse_args(["--arch", arch, "--seed", str(seed)])
    t0 = time.perf_counter()
    cfg, bundle, params = serve.load_model(args)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    param_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    engine = serve.build_engine(args, cfg, bundle, params)
    prompts, check_prompt = prompts_for(cfg.vocab_size, seed)
    if cfg.name in FLOAT32_CHECK:
        # the same engine path on a float32 copy of the weights
        import jax.numpy as jnp
        params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            engine32 = serve.build_engine(args, cfg, bundle, params32)
            _, _, abs_err, rel_err = served_check(engine32, bundle, params32,
                                                  cfg, check_prompt)
        del engine32, params32
    else:
        _, _, abs_err, rel_err = served_check(engine, bundle, params, cfg,
                                              check_prompt)
    outs, serve_s = serve_all(engine, cfg, prompts)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "model": cfg.name, "param_bytes": param_bytes,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "init_s": init_s, "compile_s": meter.seconds - compile0,
        "compile_cache_hits": meter.cache_hits - hits0,
        "requests": len(outs), "tokens_served": sum(map(len, outs)),
        "prompt_tokens": sum(map(len, prompts)), "serve_s": serve_s,
        "logits_max_abs_err": abs_err, "logits_max_rel_err": rel_err,
        "logits_check_dtype": ("float32" if cfg.name in FLOAT32_CHECK
                               else "bfloat16"),
    }), flush=True)


def run_mesh(n: int, seed: int, meter: CompileMeter) -> None:
    """chatglm3-6b on an ``n``-way TP mesh vs its one-chip run."""
    import jax

    from repro.launch import serve

    arch = MODELS[0]
    if len(jax.devices()) < n:
        sys.exit(f"chip_smoke: --mesh {n} needs {n} devices, JAX found "
                 f"{len(jax.devices())}")
    args = serve.parse_args(["--arch", arch, "--seed", str(seed)])
    cfg, bundle, params = serve.load_model(args)
    prompts, check_prompt = prompts_for(cfg.vocab_size, seed)
    one = serve.build_engine(args, cfg, bundle, params)
    tokens1, logits1, _, _ = served_check(one, bundle, params, cfg,
                                          check_prompt)
    outs1, serve1_s = serve_all(one, cfg, prompts)
    # Device 0 cannot hold the whole model and its own shard at once:
    # park the weights on the host and let the mesh engine place shards.
    host_params = jax.device_get(params)
    del one, params
    gc.collect()

    compile0 = meter.seconds
    mesh_args = serve.parse_args(["--arch", arch, "--seed", str(seed),
                                  "--mesh", str(n)])
    tp = serve.build_engine(mesh_args, cfg, bundle, host_params)
    from repro.serve.logits_check import logits_error, served_logits
    tokens_n, logits_n = served_logits(tp, check_prompt, CHECK_STEPS)
    abs_err, rel_err = logits_error(logits_n, logits1, cfg.vocab_size)
    outs_n, serve_n_s = serve_all(tp, cfg, prompts)
    print(json.dumps({
        "model": cfg.name, "mesh": n, "kv_repeat": tp.tp_plan.kv_repeat,
        "weight_bytes_per_device": tp.weight_bytes_per_device(),
        "kv_pool_bytes_per_device": tp.kv_pool_bytes_per_device(),
        "compile_s": meter.seconds - compile0,
        "check_tokens_equal": tokens_n == tokens1,
        "served_tokens_equal": outs_n == outs1,
        "tokens_served": sum(map(len, outs_n)),
        "serve_1chip_s": serve1_s, "serve_mesh_s": serve_n_s,
        "logits_max_abs_err_vs_1chip": abs_err,
        "logits_max_rel_err_vs_1chip": rel_err,
    }), flush=True)
    tol = TOLERANCE[cfg.name]
    if tokens_n != tokens1 or outs_n != outs1:
        sys.exit(f"chip_smoke: {n}-way mesh tokens differ from one chip")
    if not rel_err <= tol:
        sys.exit(f"chip_smoke: {n}-way mesh logits differ from one chip by "
                 f"{rel_err} of their range (tolerance {tol})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", type=int, default=1,
                    help="run only the tensor-parallel path on N chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()
    if not (REPO / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: the repro package is not at {REPO / 'src'}; "
                 "run this script from a checkout of the repository")
    sys.path.insert(0, str(REPO / "src"))
    # the tuned-kernel cache is read from the checkout, never from $HOME
    os.environ["REPRO_TUNE_CACHE"] = str(REPO / ".repro_cache"
                                         / "tune_cache.json")
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX's first device is "
                 f"{devices[0].platform!r} ({devices[0].device_kind}); this "
                 "script runs only on a TPU")
    print(f"devices: {len(devices)} x {devices[0].device_kind}; "
          f"compile cache: {cache_dir}", flush=True)
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.mesh > 1:
        run_mesh(args.mesh, args.seed, meter)
    else:
        for arch in MODELS:
            run_model(arch, args.seed, meter)
            gc.collect()
    print(f"total_s={time.perf_counter() - t0} "
          f"compile_s={meter.seconds} compile_cache_hits={meter.cache_hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
