"""Cross-engine greedy token-identity matrix.

One parametrized sweep covers every serving engine variant —

    {slot, paged, paged+graph, speculative} x {fp32, int8 weights}
        x {bf16 KV, int8 KV} x {prefix sharing off, on}

— over one shared-system-prompt trace and asserts every cell emits exactly
the same greedy tokens as the golden reference (the plain paged engine:
fp32 weights, bf16 KV, sharing off).  This consolidates the per-feature
identity tests that accumulated across PRs (paged-vs-slot, int8-weight and
int8-KV top-1 agreement, graph prefill, speculative ngram) into a single
matrix, so a new engine axis extends the sweep instead of adding another
ad-hoc pairwise test.

Greedy identity is the repo-wide acceptance invariant: every serving
optimization (paging, chunked prefill, quantization, fused graph prefill,
speculative verify, prefix sharing + copy-on-write) must be invisible in
the emitted tokens.

The trace is deliberately adversarial for the *sharing* axis: every prompt
is one shared two-page head plus a unique tail, and the engine runs with
more requests than slots — so the matrix exercises prefix matching, the
concurrent-prefill retro-dedup path, and the COW split at the divergence
boundary, while still requiring byte-identical outputs.

The *mesh* axis re-runs the matrix on a 4-way tensor-parallel device mesh
(`repro.parallel.tp`): same cells, TP-divisible head geometry, and each
sharded cell compared to its *unsharded twin* — a 1-device paged engine
with the same (weights, kv_dtype) — token for token.  The twin, not the
bf16 golden, is the right reference for this axis: sharding must be
invisible *given* the cell's precision config (and in fact the mesh
forward is bitwise-identical to 1 device, see `tp_einsum`), whereas
int8-KV rounding may legitimately flip a low-margin token on the lifted
geometry just as it may on any new geometry.  The bf16-vs-int8 token
identity is locked by the 1-device matrix above on the default geometry.
The mesh cells need 4 devices and therefore only run under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI ``mesh``
leg); on a plain single-device run they skip.

The *family* axis runs the recurrent-state families — rwkv6 (token-shift
+ wkv state), mamba2 (pure SSD state machine), zamba2 (hybrid: shared
attention over KV pages + mamba state slots) — through the same matrix
discipline: {paged, paged+graph, speculative} against a slot-engine
golden per family.  The hybrid's graph cell asserts the documented
rejection instead (its f32 SSD update is FMA-contraction sensitive at
cluster boundaries, so graph execution can't guarantee token identity —
see ``PagedServeEngine``).  Prefix sharing is structurally unsupported there (a
state is a lossy running summary), so instead of a sharing-on cell the
axis asserts the loud rejection; likewise the mesh leg asserts these
families reject a TP mesh instead of silently running unsharded.
"""
import dataclasses

import jax
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.parallel import ParallelContext
from repro.serve import PagedServeEngine, Request, ServeEngine
from repro.spec import SpeculativeServeEngine

PCTX = ParallelContext(None)

#: shared 2-page head (page_size=8) every request starts with
_HEAD = [2 + (j % 5) for j in range(16)]

#: engine geometry shared by every paged-family cell
_PAGED_KW = dict(slots=2, page_size=8, num_pages=16, prefill_chunk=8)

#: (engine, weights, kv_dtype) cells; every cell runs sharing off AND on
MATRIX = [
    ("paged", "fp32", "bfloat16"),
    ("paged", "int8", "bfloat16"),
    ("paged", "fp32", "int8"),
    ("paged", "int8", "int8"),
    ("graph", "fp32", "bfloat16"),
    ("graph", "int8", "bfloat16"),
    ("spec", "fp32", "bfloat16"),
    ("spec", "int8", "bfloat16"),
    ("spec", "fp32", "int8"),
]


def _trace(n=3, max_new=6):
    return [Request(rid=i, prompt=_HEAD + [50 + i] * 4, max_new_tokens=max_new)
            for i in range(n)]


def _drain(eng):
    reqs = _trace()
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    return [r.output for r in reqs]


def _build(engine, bundle, params, *, kv_dtype, sharing, pctx=PCTX):
    kw = dict(_PAGED_KW, kv_dtype=kv_dtype, prefix_sharing=sharing)
    if engine == "paged":
        return PagedServeEngine(bundle, params, pctx, **kw)
    if engine == "graph":
        return PagedServeEngine(bundle, params, pctx, use_graph=True, **kw)
    assert engine == "spec"
    return SpeculativeServeEngine(bundle, params, pctx, spec_k=3, **kw)


@pytest.fixture(scope="module")
def llama():
    cfg = get_config("llama3-8b", smoke=True)
    bundle = build_model(cfg)
    return bundle, bundle.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def qparams(llama):
    bundle, params = llama
    return bundle.quantize_params(params)


@pytest.fixture(scope="module")
def golden(llama):
    """The matrix reference: plain paged engine, fp32, bf16 KV, no sharing."""
    bundle, params = llama
    return _drain(PagedServeEngine(bundle, params, PCTX, **_PAGED_KW))


@pytest.mark.slow
@pytest.mark.parametrize("engine,weights,kv_dtype", MATRIX,
                         ids=[f"{e}-{w}w-{k}kv" for e, w, k in MATRIX])
def test_identity_matrix(engine, weights, kv_dtype, llama, qparams, golden):
    bundle, params = llama
    p = qparams if weights == "int8" else params

    out_off = _drain(_build(engine, bundle, p,
                            kv_dtype=kv_dtype, sharing=False))
    assert out_off == golden, (engine, weights, kv_dtype, "sharing off")

    eng = _build(engine, bundle, p, kv_dtype=kv_dtype, sharing=True)
    out_on = _drain(eng)
    assert out_on == golden, (engine, weights, kv_dtype, "sharing on")

    # sharing must actually have engaged on this trace (prefix hits on the
    # late admission, retro-dedup between the concurrent first two)
    m = eng.metrics
    shared = (m.prefix_hit_requests + m.cow_copies
              + eng.kv.stats["dedup_reclaimed"])
    assert shared > 0, "prefix sharing never engaged"
    assert m.effective_kv_multiplier > 1.0
    assert eng.kv.used_pages == 0        # all requests flushed on finish

    if engine == "graph":
        summary = eng._prefill.executor.graph.summary()
        assert summary["n_fused"] > 0
        assert summary["n_nodes"] < summary["n_primitive_ops"]
        # the clustering was chosen by the cost model (on by default) and
        # the decision artifact rides on the executor for --explain
        schedule = eng._prefill.executor.schedule
        assert schedule is not None
        assert schedule.passes and schedule.traffic_reduction > 1.0
        if weights == "int8":
            g = eng._prefill.executor.graph
            assert any(bn.op == "quant_matmul"
                       for n in g.nodes for bn in n.body_nodes())
            assert "fold_quant_dequant" in schedule.passes


@pytest.mark.slow
@pytest.mark.parametrize("weights", ["fp32", "int8"])
def test_slot_engine_matches_matrix_reference(weights, llama, qparams, golden):
    """The contiguous slot engine (no paging, no sharing axis) anchors the
    matrix to the numerics baseline for both weight precisions."""
    bundle, params = llama
    p = qparams if weights == "int8" else params
    eng = ServeEngine(bundle, p, PCTX, slots=2, max_seq=64)
    assert _drain(eng) == golden


# ---------------------------------------------------------------------------
# mesh axis: the whole matrix again, 4-way tensor parallel
# ---------------------------------------------------------------------------

requires_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


@pytest.fixture(scope="module")
def tp_llama():
    """Llama smoke lifted to a 4-shardable head layout (h=8, hkv=4); the
    default smoke geometry (h=4, hkv=2) doesn't divide over 4 shards."""
    cfg = get_config("llama3-8b", smoke=True)
    cfg = dataclasses.replace(cfg, num_heads=8, num_kv_heads=4,
                              head_dim=cfg.resolved_head_dim)
    bundle = build_model(cfg)
    return bundle, bundle.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tp_qparams(tp_llama):
    bundle, params = tp_llama
    return bundle.quantize_params(params)


@pytest.fixture(scope="module")
def tp_reference(tp_llama, tp_qparams):
    """Memoized 1-device twin per (weights, kv_dtype): the plain paged
    engine on the lifted geometry with the cell's own precision config.
    The mesh invariant is sharded == unsharded twin (bitwise, in fact),
    *not* lifted == default and not int8 == bf16 on this geometry."""
    bundle, params = tp_llama
    cache = {}

    def ref(weights, kv_dtype):
        key = (weights, kv_dtype)
        if key not in cache:
            p = tp_qparams if weights == "int8" else params
            cache[key] = _drain(PagedServeEngine(
                bundle, p, PCTX, kv_dtype=kv_dtype, **_PAGED_KW))
        return cache[key]

    return ref


@pytest.fixture(scope="module")
def mesh4():
    from repro.parallel import make_serving_mesh, make_tp_context
    return make_tp_context(make_serving_mesh(4))


@requires_mesh
@pytest.mark.slow
@pytest.mark.parametrize("engine,weights,kv_dtype", MATRIX,
                         ids=[f"{e}-{w}w-{k}kv" for e, w, k in MATRIX])
def test_identity_matrix_mesh4(engine, weights, kv_dtype,
                               tp_llama, tp_qparams, tp_reference, mesh4):
    bundle, params = tp_llama
    p = tp_qparams if weights == "int8" else params

    if engine == "graph":
        # the graph executor is a host-side op loop; a TP mesh must be
        # rejected loudly at construction, not silently run unsharded
        with pytest.raises(ValueError, match="TP mesh"):
            _build(engine, bundle, p, kv_dtype=kv_dtype, sharing=False,
                   pctx=mesh4)
        return

    twin = tp_reference(weights, kv_dtype)
    eng = None
    for sharing in (False, True):
        eng = _build(engine, bundle, p, kv_dtype=kv_dtype, sharing=sharing,
                     pctx=mesh4)
        out = _drain(eng)
        assert out == twin, (engine, weights, kv_dtype,
                             f"mesh4 sharing={sharing}")
        assert eng.kv.used_pages == 0

    # the cells really ran sharded: 4-way plan, KV pool bytes per device
    # at least 3x below the logical pool (hkv=4 shards exactly 4x)
    assert eng.tp_plan is not None and eng.tp_plan.degree == 4
    assert eng.tp_plan.kv_repeat == 1
    assert eng.kv_pool_bytes_per_device() * 3 <= eng.kv_pool_bytes()
    assert eng.weight_bytes_per_device() * 2 <= _tree_bytes(eng.params)


def _tree_bytes(tree):
    return sum(a.nbytes for a in jax.tree.leaves(tree)
               if hasattr(a, "nbytes"))


# ---------------------------------------------------------------------------
# family axis: recurrent/hybrid families, same discipline
# ---------------------------------------------------------------------------

#: attention-free with token-shift state / pure SSD state machine / hybrid
#: (shared attention over KV pages + mamba state slots in one block table)
FAMILY_ARCHS = ["rwkv6-3b", "mamba2-2.7b", "zamba2-1.2b"]

#: (engine,) cells per family; sharing is structurally unsupported for
#: recurrent state, so the sharing axis is a rejection test instead
FAMILY_CELLS = ["paged", "graph", "spec"]


@pytest.fixture(scope="module", params=FAMILY_ARCHS)
def family(request):
    cfg = get_config(request.param, smoke=True)
    bundle = build_model(cfg)
    return bundle, bundle.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def family_golden(family):
    """Per-family numerics baseline: the contiguous slot engine, which
    carries recurrent state as dense per-slot registers with no paging,
    no checkpoints, no graph in the loop."""
    bundle, params = family
    return _drain(ServeEngine(bundle, params, PCTX, slots=2, max_seq=64))


@pytest.mark.slow
@pytest.mark.parametrize("engine", FAMILY_CELLS)
def test_family_identity_matrix(engine, family, family_golden):
    bundle, params = family
    if engine == "graph" and bundle.cfg.family == "hybrid":
        # cluster-at-a-time execution cannot guarantee token identity for
        # the hybrid's FMA-contraction-sensitive f32 SSD update: the cell
        # is a loud rejection, not a silent near-miss
        with pytest.raises(ValueError, match="use_graph.*hybrid"):
            _build(engine, bundle, params, kv_dtype="bfloat16",
                   sharing=False)
        return
    eng = _build(engine, bundle, params, kv_dtype="bfloat16", sharing=False)
    assert _drain(eng) == family_golden, (bundle.cfg.name, engine)

    # state pool drained leak-free: every slot's current id + ring
    # checkpoints released on finish, pages (hybrid) flushed too
    assert eng.state is not None and eng.state.used_slots == 0
    assert eng.kv.used_pages == 0

    if engine == "graph":
        # both compiled steps really fused (the decode tick is the new one)
        for step in (eng._prefill, eng._decode_step):
            summary = step.executor.graph.summary()
            assert summary["n_fused"] > 0
            assert summary["n_nodes"] < summary["n_primitive_ops"]
    if engine == "spec":
        # rollbacks happened and were invisible: every speculative step
        # restored a state checkpoint (accepted-count snapshot -> cur)
        assert eng.state.stats["restores"] > 0


@pytest.mark.slow
def test_family_rejects_prefix_sharing(family):
    """A recurrent state is a lossy running summary, not an addressable
    prefix — sharing must be rejected loudly at construction."""
    bundle, params = family
    with pytest.raises(ValueError, match="prefix_sharing"):
        _build("paged", bundle, params, kv_dtype="bfloat16", sharing=True)


@requires_mesh
@pytest.mark.slow
def test_family_rejects_tp_mesh(family, mesh4):
    """State pools are per-sequence registers, not head-sharded tensors;
    a TP mesh must be rejected, not silently run unsharded."""
    bundle, params = family
    with pytest.raises(ValueError, match="TP"):
        _build("paged", bundle, params, kv_dtype="bfloat16", sharing=False,
               pctx=mesh4)
