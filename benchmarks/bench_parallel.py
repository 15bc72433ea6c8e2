"""Tensor-parallel serving benchmark: 1 device vs an N-way device mesh.

Drives the same shared-system-prompt trace through the paged engine twice —

* ``single`` — 1-device :class:`PagedServeEngine` (the matrix reference),
* ``mesh``   — the same engine over an N-way tensor-parallel mesh
  (`repro.parallel.tp`): attention heads, MLP blocks and the KV page
  pools sharded over N devices, block tables / allocator / prefix cache
  staying host-side and single-source

— and writes ``BENCH_parallel.json`` (schema in benchmarks/README.md).
The headline numbers are the per-device footprint reductions: the KV page
pool and the weights each device holds must shrink ~Nx versus the logical
single-device arrays, while the emitted greedy tokens stay identical
token for token (the repo-wide acceptance invariant — sharding must be
invisible in the outputs, see docs/parallel.md for why the split-K
contraction makes that bitwise).

Gates (exit 1 on violation):

* greedy tokens identical between the 1-device and mesh engines,
* per-device KV-pool bytes reduced >= 3x at mesh=4 (KV heads shard
  exactly Nx when ``num_kv_heads % N == 0``),
* per-device weight bytes reduced >= 2x (embeddings stay replicated, so
  the weight reduction is sublinear at smoke scale).

On a CPU-only machine the N devices are simulated
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``, injected before
jax loads — same mechanism as ``repro.launch.serve --mesh N``).

    PYTHONPATH=src python benchmarks/bench_parallel.py --quick
"""
import argparse
import datetime
import json
import os
import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
for _p in (str(_REPO / "src"), str(_REPO / "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

SCHEMA_VERSION = 1

MIN_KV_REDUCTION = 3.0
MIN_WEIGHT_REDUCTION = 2.0


def _ensure_devices(n: int) -> None:
    """Must run before jax initialises its backends."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    m = eng.run_until_drained()
    assert all(r.done for r in reqs)
    return [r.output for r in reqs], m


def bench(*, mesh_n, arch, requests, max_new, slots, page_size,
          prefill_chunk, kv_dtype):
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.parallel import (ParallelContext, make_serving_mesh,
                                make_tp_context)
    from repro.serve import PagedServeEngine, Request

    cfg = get_config(arch, smoke=True)
    if cfg.num_heads % mesh_n or cfg.num_kv_heads % mesh_n \
            or cfg.d_ff % mesh_n:
        # lift the smoke geometry to a TP-divisible head layout, same as
        # repro.launch.serve --mesh (full-size configs divide naturally)
        up = lambda v, n: -(-v // n) * n
        hkv = up(cfg.num_kv_heads, mesh_n)
        cfg = dataclasses.replace(
            cfg, num_kv_heads=hkv,
            num_heads=up(max(cfg.num_heads, hkv), hkv),
            head_dim=cfg.resolved_head_dim, d_ff=up(cfg.d_ff, mesh_n))
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))

    head = [2 + (j % 5) for j in range(2 * page_size)]
    trace = lambda: [Request(rid=i, prompt=head + [50 + i] * 4,
                             max_new_tokens=max_new)
                     for i in range(requests)]
    kw = dict(slots=slots, page_size=page_size, prefill_chunk=prefill_chunk,
              kv_dtype=kv_dtype)

    single = PagedServeEngine(bundle, params, ParallelContext(None), **kw)
    out_1, m_1 = _drain(single, trace())
    kv_bytes_1 = single.kv_pool_bytes()
    w_bytes_1 = sum(a.nbytes for a in jax.tree.leaves(single.params)
                    if hasattr(a, "nbytes"))

    pctx = make_tp_context(make_serving_mesh(mesh_n))
    sharded = PagedServeEngine(bundle, params, pctx, **kw)
    out_n, m_n = _drain(sharded, trace())

    kv_dev = sharded.kv_pool_bytes_per_device()
    w_dev = sharded.weight_bytes_per_device()
    return {
        "schema_version": SCHEMA_VERSION,
        "generated_at": datetime.datetime.now().isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "arch": arch,
        "mesh": mesh_n,
        "devices": [str(d) for d in pctx.mesh.devices.flat],
        "geometry": {"num_heads": cfg.num_heads,
                     "num_kv_heads": cfg.num_kv_heads,
                     "d_ff": cfg.d_ff, "d_model": cfg.d_model},
        "workload": {"requests": requests, "prompt_len": len(head) + 4,
                     "max_new": max_new, "slots": slots,
                     "page_size": page_size, "prefill_chunk": prefill_chunk,
                     "kv_dtype": kv_dtype},
        "single": {"kv_pool_bytes": kv_bytes_1, "weight_bytes": w_bytes_1,
                   "decode_tps": round(m_1.decode_tps, 2)},
        "mesh_engine": {"kv_pool_bytes_per_device": kv_dev,
                        "weight_bytes_per_device": w_dev,
                        "tp_degree": sharded.tp_plan.degree,
                        "kv_repeat": sharded.tp_plan.kv_repeat,
                        "decode_tps": round(m_n.decode_tps, 2)},
        "kv_bytes_reduction": round(kv_bytes_1 / max(kv_dev, 1), 3),
        "weight_bytes_reduction": round(w_bytes_1 / max(w_dev, 1), 3),
        "outputs_identical": out_1 == out_n,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized trace (fewer/shorter requests)")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--mesh", type=int, default=4)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--kv-dtype", choices=("bfloat16", "int8"),
                    default="bfloat16")
    ap.add_argument("--out", default=str(_REPO / "BENCH_parallel.json"))
    args = ap.parse_args()
    _ensure_devices(args.mesh)  # before any jax import
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    defaults = ((3, 6) if args.quick else (6, 12))
    report = bench(mesh_n=args.mesh, arch=args.arch,
                   requests=args.requests or defaults[0],
                   max_new=args.max_new or defaults[1],
                   slots=args.slots, page_size=args.page_size,
                   prefill_chunk=args.prefill_chunk, kv_dtype=args.kv_dtype)
    Path(args.out).write_text(
        json.dumps(report, indent=2, sort_keys=True) + "\n")

    m = report["mesh_engine"]
    print(f"wrote {args.out} (backend={report['backend']}, mesh={report['mesh']}, "
          f"outputs_identical={report['outputs_identical']})")
    print(f"  kv pool:  {report['single']['kv_pool_bytes']}B logical -> "
          f"{m['kv_pool_bytes_per_device']}B/device "
          f"({report['kv_bytes_reduction']:.2f}x)")
    print(f"  weights:  {report['single']['weight_bytes']}B -> "
          f"{m['weight_bytes_per_device']}B/device "
          f"({report['weight_bytes_reduction']:.2f}x)")
    print(f"  decode tok/s: single={report['single']['decode_tps']:.1f}  "
          f"mesh={m['decode_tps']:.1f} (simulated devices share one host)")

    failed = False
    if not report["outputs_identical"]:
        print("FAIL: mesh engine emitted different greedy tokens than the "
              "1-device engine", file=sys.stderr)
        failed = True
    if report["kv_bytes_reduction"] < MIN_KV_REDUCTION:
        print(f"FAIL: per-device KV pool reduction "
              f"{report['kv_bytes_reduction']:.2f}x < "
              f"{MIN_KV_REDUCTION}x gate", file=sys.stderr)
        failed = True
    if report["weight_bytes_reduction"] < MIN_WEIGHT_REDUCTION:
        print(f"FAIL: per-device weight reduction "
              f"{report['weight_bytes_reduction']:.2f}x < "
              f"{MIN_WEIGHT_REDUCTION}x gate", file=sys.stderr)
        failed = True
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
