"""Multi-device correctness checks (run in a subprocess with 8 host devices;
invoked by test_distributed.py).  Each check prints 'CHECK <name> OK'."""
import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    "--xla_disable_hlo_passes=all-reduce-promotion")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.checkpoint import CheckpointManager
from repro.models import build_model
from repro.models.moe import moe_block
from repro.parallel.compression import (compressed_value_and_grad,
                                        dequantize_int8, quantize_int8)
from repro.parallel.pipeline_parallel import pipeline_apply
from repro.parallel.sharding import make_context
from repro.runtime.elastic import build_mesh, remesh_restore
from repro.train.step import TrainHyper, assemble_shardings, init_optimizer, make_train_step


def check_moe_ep_matches_local():
    """MoE with shard_map all-to-all EP == single-device routing math."""
    mesh = build_mesh(8, model_parallel=2)
    pctx = make_context(mesh)
    cfg = get_config("arctic-480b", smoke=True)
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    moe_p = {k[len("blk.0."):]: v[0] for k, v in params.items()
             if k.startswith("blk.0.moe")}
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)

    local = moe_block(moe_p, "moe", cfg, x, None)
    with jax.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P(("data",), None, None)))
        dist = jax.jit(lambda p, v: moe_block(p, "moe", cfg, v, pctx))(moe_p, xs)
    err = float(jnp.max(jnp.abs(local.astype(jnp.float32) - dist.astype(jnp.float32))))
    # identical math up to all-to-all reordering of bf16 adds
    assert err < 0.15, f"moe mismatch {err}"
    # token conservation: mean outputs comparable
    assert abs(float(local.mean()) - float(dist.mean())) < 1e-2
    print("CHECK moe_ep OK", err)


def check_pipeline_parallel():
    """4-stage GPipe == sequential layer application, fwd and grad."""
    mesh = jax.make_mesh((4,), ("stage",))
    L, M, mb, d = 4, 6, 3, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (L, d, d), jnp.float32) * 0.3

    def layer(w, x):
        return jnp.tanh(x @ w)

    xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d), jnp.float32)
    with jax.set_mesh(mesh):
        out_pp = pipeline_apply(layer, ws, xs, mesh)
    ref = xs
    for i in range(L):
        ref = jax.vmap(lambda x: layer(ws[i], x))(ref)
    np.testing.assert_allclose(np.asarray(out_pp), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    # gradients flow through ppermute
    def loss_pp(ws):
        return jnp.sum(pipeline_apply(layer, ws, xs, mesh) ** 2)

    def loss_ref(ws):
        r = xs
        for i in range(L):
            r = layer(ws[i], r)
        return jnp.sum(r ** 2)

    with jax.set_mesh(mesh):
        g_pp = jax.grad(loss_pp)(ws)
    g_ref = jax.grad(loss_ref)(ws)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
    print("CHECK pipeline_parallel OK")


def check_compression():
    """int8 quant roundtrip error bound + compressed cross-pod grads close
    to exact grads."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,), jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s, x.shape) - x))
    assert err.max() <= float(np.abs(np.asarray(x)).max()) / 127 + 1e-6

    mesh = build_mesh(8, model_parallel=2, pods=2)
    pctx = make_context(mesh)
    cfg = get_config("llama3-8b", smoke=True)
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 255),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 255),
    }
    from repro.train.step import _loss_fn
    import functools
    loss_fn = functools.partial(_loss_fn, bundle, pctx)
    with jax.set_mesh(mesh):
        l_exact, g_exact = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        l_c, g_c = jax.jit(lambda p, b: compressed_value_and_grad(
            loss_fn, p, b, pctx, enabled=True))(params, batch)
    assert abs(float(l_exact) - float(l_c)) < 1e-2
    rel = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
           / (float(jnp.max(jnp.abs(a.astype(jnp.float32)))) + 1e-9)
           for a, b in zip(jax.tree.leaves(g_exact), jax.tree.leaves(g_c))]
    assert max(rel) < 0.15, f"compressed grads too far: {max(rel)}"
    print("CHECK compression OK", max(rel))


def check_elastic_remesh():
    """Train 3 steps on 8 devices, checkpoint, restore onto 4 devices and
    continue — losses keep decreasing."""
    import tempfile
    cfg = get_config("llama3-8b", smoke=True)
    bundle = build_model(cfg)
    shape = ShapeSpec("t", 32, 8, "train")

    def setup(mesh):
        pctx = make_context(mesh)
        params = bundle.init_params(jax.random.PRNGKey(0))
        opt = init_optimizer(cfg, params)
        pspecs, opt_fn, _ = assemble_shardings(bundle, pctx)
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
        osh = jax.tree.map(lambda s: NamedSharding(mesh, s), opt_fn(opt),
                           is_leaf=lambda x: isinstance(x, P))
        params = jax.tree.map(jax.device_put, params, psh)
        opt = jax.tree.map(jax.device_put, opt, osh)
        step = jax.jit(make_train_step(bundle, pctx, TrainHyper(peak_lr=1e-2, warmup=1)))
        return params, opt, step, (pspecs, opt_fn)

    from repro.data import SyntheticLMSource
    src = SyntheticLMSource(cfg, shape)

    mesh8 = build_mesh(8, model_parallel=2)
    params, opt, step, (pspecs, opt_fn) = setup(mesh8)
    losses = []
    for i in range(3):
        batch = {k: jnp.asarray(v) for k, v in src.batch_at(i).items()}
        params, opt, m = step(params, opt, batch, jnp.asarray(i, jnp.int32))
        losses.append(float(m["loss"]))

    with tempfile.TemporaryDirectory() as d:
        ckpt = CheckpointManager(d)
        ckpt.save(3, (params, opt), metadata={"cursor": {"step": 3, "seed": 0}})

        mesh4 = build_mesh(4, model_parallel=2)
        pctx4 = make_context(mesh4)
        opt_abs = jax.eval_shape(lambda p: init_optimizer(cfg, p),
                                 bundle.abstract_params())
        spec_tree = (pspecs, opt_fn(opt_abs))
        (params4, opt4), meta, pctx4 = remesh_restore(
            ckpt, (params, opt), spec_tree, mesh4)
        assert meta["cursor"]["step"] == 3
        step4 = jax.jit(make_train_step(bundle, pctx4, TrainHyper(peak_lr=1e-2, warmup=1)))
        for i in range(3, 6):
            batch = {k: jnp.asarray(v) for k, v in src.batch_at(i).items()}
            params4, opt4, m = step4(params4, opt4, batch, jnp.asarray(i, jnp.int32))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    print("CHECK elastic_remesh OK", [round(l, 3) for l in losses])


def check_tp_kv_repeat():
    """Fewer KV heads than shards (chatglm3's hkv=2 over 4): the TP engine
    repeats each KV head so every shard attends to its own group's head,
    and serves the 1-device engine's greedy tokens."""
    import dataclasses

    from repro.parallel import ParallelContext
    from repro.parallel.tp import make_serving_mesh, make_tp_context
    from repro.serve import PagedServeEngine, Request

    cfg = dataclasses.replace(get_config("chatglm3-6b", smoke=True),
                              num_heads=8, num_kv_heads=2, d_ff=128)
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))

    def serve(pctx):
        eng = PagedServeEngine(bundle, params, pctx, slots=2, page_size=8,
                               num_pages=16, prefill_chunk=8)
        reqs = [Request(rid=i, prompt=[3 + i, 7, 9, 11, 2 + i] * 3,
                        max_new_tokens=8) for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        return eng, [r.output for r in reqs]

    _, one = serve(ParallelContext(None))
    eng, tp = serve(make_tp_context(make_serving_mesh(4)))
    assert eng.tp_plan.kv_repeat == 2, eng.tp_plan
    assert tp == one, (tp, one)
    # each device holds one KV head of every page: half the 2-head pool
    assert eng.kv_pool_bytes_per_device() * 4 == eng.kv_pool_bytes()
    print("CHECK tp_kv_repeat OK")


CHECKS = {
    "moe_ep": check_moe_ep_matches_local,
    "pipeline_parallel": check_pipeline_parallel,
    "compression": check_compression,
    "elastic_remesh": check_elastic_remesh,
    "tp_kv_repeat": check_tp_kv_repeat,
}

if __name__ == "__main__":
    names = sys.argv[1:] or list(CHECKS)
    for n in names:
        CHECKS[n]()
    print("ALL DIST CHECKS OK")
