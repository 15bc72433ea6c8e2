"""Serving engines: paged continuous batching (production path) and the
contiguous slot engine (reference baseline).

:class:`PagedServeEngine` is the serve-side mirror of the paper's APR
residency story: KV lives in fixed-size reusable pages (``paged_cache``), a
FIFO scheduler streams prompts through token-budgeted *chunked prefill*
(``bundle.decode_paged`` with T = chunk, not the token-by-token decode
loop), decode attention touches only live pages, and a finished request's
pages flush back to the pool in one step.  Recurrent-state families
(ssm/mamba/hybrid) serve through the same engine: their fixed-size state
lives in a :class:`repro.serve.state_cache.StateCache`-managed slot pool
appended to the block table (read column + per-token write columns), and
hybrid (zamba2) slots hold KV pages *and* a state slot, rolled back
atomically by :meth:`PagedServeEngine._truncate_slot`.  :class:`ServeEngine`
keeps the seed slot engine — one contiguous ``slots x max_seq`` cache,
prefill through the decode path — as the numerics baseline the paged engine
is tested against (token-identical greedy outputs) and as the fallback for
families with no paged serving path at all (audio).

Both engines route kernel-config resolution through the tuned-config
cache; an explicit ``tune_cache`` argument is scoped to the engine
(:func:`repro.bench.config.scoped_cache` around warm-up and every
``step()``), so engines with different tuned profiles — different dtypes,
different hardware assumptions — coexist in one process.

Speculative decoding layers on top of the paged engine rather than living
here: :class:`repro.spec.SpeculativeServeEngine` subclasses
:class:`PagedServeEngine`, replacing the one-token decode tick with a
draft-and-verify step (T = spec_k + 1 through the same ``decode_paged``
contract) and rolling rejected tokens back via
:meth:`repro.serve.paged_cache.PagedKVCache.truncate`.  The spec fields on
:class:`EngineMetrics` below stay zero on the plain engines.
"""
from __future__ import annotations

import dataclasses
import queue
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..bench.autotune import warm_cache
from ..bench.config import ConfigCache, scoped_cache
from ..models.registry import ModelBundle
from ..parallel.sharding import ParallelContext
from .paged_cache import OutOfPages, PagedKVCache
from .scheduler import (DECODING, DONE, PREFILLING, FifoScheduler, Request)
from .state_cache import TRASH_STATE, StateCache


@dataclasses.dataclass
class EngineMetrics:
    """Aggregate serving metrics, accumulated per tick by the engine."""
    ticks: int = 0
    requests_done: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    preemptions: int = 0
    prefill_time_s: float = 0.0   # device time inside prefill-chunk calls
    decode_time_s: float = 0.0    # device time inside decode-tick calls
    started_at: float = dataclasses.field(default_factory=time.perf_counter)
    ttfts: List[float] = dataclasses.field(default_factory=list)
    util_samples: List[float] = dataclasses.field(default_factory=list)
    # speculative decoding (repro.spec); all zero on the plain engine
    spec_steps: int = 0           # verify steps, counted per participating
                                  #   request (a batched tick adds one per
                                  #   DECODING slot it verified)
    draft_proposed: int = 0       # draft tokens proposed across all steps
    draft_accepted: int = 0       # ... accepted by the target AND emitted
    draft_time_s: float = 0.0     # time spent producing draft proposals
    # prefix cache (prefix_sharing=True); hit/cow stay zero without it
    prefix_hit_tokens: int = 0    # prompt tokens whose KV came from the
                                  #   prefix cache instead of prefill
    prefix_hit_requests: int = 0  # admissions that matched a cached prefix
    cow_copies: int = 0           # device page copies from COW splits
    prompt_pages_logical: int = 0  # sum over admissions of the pages each
                                   #   prompt would cost without sharing
    prompt_pages_unique: int = 0   # net new physical pages prefill actually
                                   #   consumed (fresh + COW - dedup)

    @property
    def elapsed(self) -> float:
        return time.perf_counter() - self.started_at

    @property
    def decode_tps(self) -> float:
        """Decode tokens per second of *decode device time* (phase-local,
        so prefill pressure and host scheduling don't dilute it)."""
        return self.decode_tokens / max(self.decode_time_s, 1e-9)

    @property
    def prefill_tps(self) -> float:
        """Prompt tokens per second of *prefill device time*."""
        return self.prefill_tokens / max(self.prefill_time_s, 1e-9)

    @property
    def mean_ttft(self) -> float:
        return float(np.mean(self.ttfts)) if self.ttfts else float("nan")

    @property
    def p50_ttft(self) -> float:
        return float(np.median(self.ttfts)) if self.ttfts else float("nan")

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the target model verified."""
        return self.draft_accepted / max(self.draft_proposed, 1)

    @property
    def tokens_per_step(self) -> float:
        """Tokens emitted per verify step (1.0 would match plain decode;
        the speculative ceiling is spec_k + 1)."""
        return self.decode_tokens / max(self.spec_steps, 1)

    @property
    def spec_decode_tps(self) -> float:
        """Decode tokens per second *including* draft time — the honest
        speculative throughput to compare against a plain engine's
        ``decode_tps`` (which has no draft phase)."""
        return self.decode_tokens / max(self.decode_time_s
                                        + self.draft_time_s, 1e-9)

    @property
    def effective_kv_multiplier(self) -> float:
        """Logical prompt pages served per physical page consumed — the
        effective-KV-capacity multiplier prefix sharing buys.  1.0 means
        no sharing benefit; N means the same pool admitted N tokens of
        prompt KV per token actually materialized."""
        return self.prompt_pages_logical / max(self.prompt_pages_unique, 1)

    @property
    def peak_page_utilization(self) -> float:
        return max(self.util_samples, default=0.0)

    @property
    def mean_page_utilization(self) -> float:
        return float(np.mean(self.util_samples)) if self.util_samples else 0.0

    def summary(self) -> Dict[str, float]:
        out = {
            "ticks": self.ticks,
            "requests_done": self.requests_done,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "preemptions": self.preemptions,
            "elapsed_s": round(self.elapsed, 4),
            "prefill_time_s": round(self.prefill_time_s, 4),
            "decode_time_s": round(self.decode_time_s, 4),
            "prefill_tps": round(self.prefill_tps, 2),
            "decode_tps": round(self.decode_tps, 2),
            "mean_ttft_s": round(self.mean_ttft, 4) if self.ttfts else None,
            "p50_ttft_s": round(self.p50_ttft, 4) if self.ttfts else None,
            "peak_page_utilization": round(self.peak_page_utilization, 4),
            "mean_page_utilization": round(self.mean_page_utilization, 4),
        }
        if self.spec_steps:  # speculative fields only when spec ran
            out.update({
                "spec_steps": self.spec_steps,
                "draft_proposed": self.draft_proposed,
                "draft_accepted": self.draft_accepted,
                "draft_time_s": round(self.draft_time_s, 4),
                "acceptance_rate": round(self.acceptance_rate, 4),
                "tokens_per_step": round(self.tokens_per_step, 4),
                "spec_decode_tps": round(self.spec_decode_tps, 2),
            })
        if self.prefix_hit_requests or self.cow_copies:
            out.update({
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_hit_requests": self.prefix_hit_requests,
                "cow_copies": self.cow_copies,
                "prompt_pages_logical": self.prompt_pages_logical,
                "prompt_pages_unique": self.prompt_pages_unique,
                "effective_kv_multiplier":
                    round(self.effective_kv_multiplier, 4),
            })
        return out


class PagedServeEngine:
    """Continuous batching over a paged KV cache with chunked prefill.

    Device state: per-layer KV page pools (``bundle.init_paged_cache``) and
    two jit'd entry points — a slot-batched decode tick (T=1) and a B=1
    prefill-chunk step (T=``prefill_chunk``) — both through
    ``bundle.decode_paged``, so prefill and decode share one cache contract.
    Host state: the page allocator (``PagedKVCache``) and the FIFO
    scheduler; see ``docs/serving.md`` for the request lifecycle and the
    scheduler invariants.

    ``kv_dtype="int8"`` stores the page pools as int8 + per-(page slot,
    head) fp32 scales (quantize-on-write, dequantize-after-gather; see
    ``docs/quantization.md``), roughly halving KV memory vs bf16; pass the
    model's int8-weight params (``bundle.quantize_params``) for the weight
    side of the same trade.

    ``prefix_sharing=True`` turns on the refcounted prefix cache: at admit
    the longest already-cached prefix of the prompt is attached read-only
    (its KV is reused, not recomputed — prefill resumes after it), pages
    completed by prefill are published for later requests, and the first
    divergent write COW-splits its shared boundary page
    (:meth:`_sync_page_copies` mirrors the split on the device pools).
    Greedy outputs are token-identical to a sharing-off engine: a cached
    page's KV is byte-identical to what prefill would have recomputed
    (same tokens, same positions, deterministic forward).  See
    ``docs/serving.md`` and ``benchmarks/bench_serve.py`` for the
    effective-KV-capacity multiplier this buys on shared-system-prompt
    traffic.

    Recurrent-state families (``supports_paged_state``) carry a
    :class:`repro.serve.state_cache.StateCache` next to the page allocator:
    every admitted request owns one physical state slot (plus a snapshot
    ring for rollback), the block table grows a state-read column and T
    per-token write columns, and the same ``decode_paged`` contract serves
    rwkv6 / mamba2 / zamba2 token-identically to the slot engine.  The KV
    allocator still ledgers every family's tokens (capacity, preemption,
    per-request caps); pure recurrent models simply never read the pages.
    ``state_dtype="int8"`` stores the large running-reduction leaves
    (wkv/ssm) int8 + per-head scales — lossy across steps, so not
    token-identity gated.  ``prefix_sharing`` is rejected for these
    families: a state is a lossy running summary, so a cached prefix cannot
    be attached mid-sequence.

    ``use_graph=True`` routes the chunked-prefill step *and* the T=1
    decode tick through the ``repro.graph`` compiler: the paged decode
    contract is traced unrolled at fixed shapes, epilogue/quant fusion
    passes run, and both steps execute through the fused graph executor
    (token-identical to the jit path, CI-gated by
    ``benchmarks/bench_graph.py``; see ``docs/graph.md``).  The hybrid
    family is rejected here: its f32 SSD update is FMA-contraction
    sensitive at cluster boundaries, so graph execution cannot guarantee
    token identity (see ``compile_decode_step``).  The fusion clustering
    is chosen by the ``repro.cost`` model (``graph_cost_model=False`` or
    ``$REPRO_COST_MODEL=off`` reverts to the fixed pipeline) and the
    chosen schedule persists in this engine's ``tune_cache`` next to the
    tuned kernel tiles; :meth:`graph_schedule_report` prints the audit.
    """

    def __init__(self, bundle: ModelBundle, params, pctx: ParallelContext,
                 *, slots: int = 4, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_pages_per_slot: Optional[int] = None,
                 prefill_chunk: int = 16,
                 prefill_budget: Optional[int] = None,
                 kv_dtype: str = "bfloat16",
                 state_dtype: str = "float32",
                 prefix_sharing: bool = False,
                 use_graph: bool = False,
                 graph_impl: Optional[str] = None,
                 graph_cost_model: Optional[bool] = None,
                 tune_cache: Optional[str] = None,
                 autotune_at_start: bool = False):
        if not bundle.supports_paged_serving:
            raise ValueError(
                f"{bundle.cfg.family!r} family has no paged KV cache or "
                "state pool; use the contiguous ServeEngine")
        if kv_dtype not in ("bfloat16", "float32", "int8"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
        if state_dtype not in ("float32", "int8"):
            raise ValueError(f"unsupported state_dtype {state_dtype!r}")
        if prefix_sharing and bundle.supports_paged_state:
            raise ValueError(
                "prefix_sharing=True is unsupported for recurrent-state "
                "families: a state slot is a lossy running summary of its "
                "whole history, so a cached prefix's KV pages cannot be "
                "attached mid-sequence (there is no state to resume from)")
        if use_graph and bundle.cfg.family == "hybrid":
            raise ValueError(
                "use_graph=True is unsupported for the hybrid family: "
                "cluster boundaries are compilation boundaries, and the "
                "interleaved f32 SSD update + bf16 attention is sensitive "
                "to cross-op FMA contraction — a 1-ulp f32 shift at a "
                "cluster cut can cross a bf16 rounding boundary and flip "
                "a greedy token, breaking the token-identity invariant; "
                "serve hybrids on the plain paged engine")
        # Tensor-parallel mode: a mesh with a >1 TP axis shards attention
        # heads / MLP blocks / KV page pools across its devices; everything
        # host-side (allocator, scheduler, prefix cache, block tables) is
        # unchanged — one engine drives N devices.  See repro.parallel.tp
        # and docs/parallel.md.
        self.tp_plan = None
        mesh = pctx.mesh
        if (mesh is not None and pctx.tp_axis in mesh.axis_names
                and mesh.shape[pctx.tp_axis] > 1):
            if bundle.supports_paged_state:
                raise ValueError(
                    "recurrent-state families have no TP plan: state pools "
                    "are per-sequence registers, not head-sharded tensors; "
                    "serve ssm/mamba/hybrid without a TP mesh")
            if use_graph:
                raise ValueError(
                    "use_graph=True is incompatible with a TP mesh: the "
                    "graph executor is a host-side op loop and cannot run "
                    "inside the manual shard_map region (use the jit "
                    "prefill path on meshes)")
            from ..parallel import tp as _tp
            self._tp = _tp
            self.tp_plan = _tp.plan_tp(bundle.cfg,
                                       int(mesh.shape[pctx.tp_axis]))
        self.bundle = bundle
        self.params = params
        self.pctx = pctx
        self.slots = slots
        self.page_size = page_size
        if num_pages is None:
            num_pages = slots * max(256 // page_size, 1)
        if max_pages_per_slot is None:
            # Bound the block-table width (and with it the logical span every
            # decode/prefill gather attends over) to a 256-token per-request
            # default rather than the whole pool — the attention cost of a
            # tick scales with slots x max_pages_per_slot x page_size.
            max_pages_per_slot = min(num_pages, max(256 // page_size, 1))
        self.kv = PagedKVCache(slots=slots, num_pages=num_pages,
                               page_size=page_size,
                               max_pages_per_slot=max_pages_per_slot,
                               enable_sharing=prefix_sharing)
        self.prefix_sharing = prefix_sharing
        self.sched = FifoScheduler(prefill_chunk=prefill_chunk,
                                   prefill_budget=prefill_budget)
        self.prefill_chunk = prefill_chunk
        self.kv_dtype = kv_dtype
        self.state_dtype = state_dtype
        self.use_graph = use_graph
        # Recurrent-state slot pool: one current state per engine slot plus
        # a snapshot ring (depth spec_k+1 on the speculative engine, whose
        # subclass sets ``spec_k`` before calling up) for truncate rollback.
        self.state: Optional[StateCache] = (
            StateCache(slots=slots, ring_depth=getattr(self, "spec_k", 0) + 1)
            if bundle.supports_paged_state else None)
        # Tuned-kernel plumbing: an explicit ``tune_cache`` is scoped to
        # THIS engine (warm-up + every step()); other engines and bare
        # kernel calls keep their own resolution.  See scoped_cache.
        self.tune_cache = (ConfigCache(tune_cache)
                          if tune_cache is not None else None)
        with scoped_cache(self.tune_cache):
            self.tuned_configs = warm_cache(
                self._decode_kernel_shapes(), sweep=autotune_at_start)
        self.cache = bundle.init_paged_cache(
            self.kv.pool_pages, page_size, kv_dtype=kv_dtype,
            state_slots=(self.state.pool_slots if self.state else 0),
            state_dtype=state_dtype)
        self.active: List[Optional[Request]] = [None] * slots
        self.last_tokens = np.zeros((slots,), np.int64)
        self.metrics = EngineMetrics()
        copy_fn = lambda c, s, d: jax.tree.map(
            lambda a: a.at[:, :, d].set(a[:, :, s]), c)
        if self.tp_plan is not None:
            # Shard the device state: params per logical axes (heads/ff/
            # vocab over the TP axis), KV pools over their kv-head axis.
            # One global cache keeps the page/block-table indexing shared;
            # each device physically holds only its heads' slice.
            params, self.cache = self._tp.repeat_kv_heads(
                params, self.cache, bundle.logical_axes(), self.tp_plan)
            pspecs = self._tp.tp_param_specs(params, bundle.logical_axes(),
                                             self.tp_plan, pctx.tp_axis)
            cspecs = self._tp.tp_cache_specs(self.cache, pctx.tp_axis)
            self.params = self._tp.shard_tree(params, mesh, pspecs)
            self.cache = self._tp.shard_tree(self.cache, mesh, cspecs)
            self._decode = jax.jit(self._tp.make_tp_decode_paged(
                bundle, pctx, self.tp_plan, pspecs, cspecs))
            # pin the copy output to the pool sharding so COW/defrag moves
            # never silently gather a pool onto every device
            from jax.sharding import NamedSharding
            cache_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), cspecs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
            self._copy_page = jax.jit(copy_fn, out_shardings=cache_sh)
        else:
            self._decode = jax.jit(
                lambda p, c, t, l, n, bt: bundle.decode_paged(p, c, t, l, n, bt, pctx))
            if self.state is not None:
                # Key-aware device copies: a mixed cache holds KV page
                # pools (page axis 2) AND state pools (slot axis 1), so
                # page moves and state moves each touch only their leaves
                # (repro.models.paged_state).
                from ..models.paged_state import copy_kv_page, copy_state_slot
                self._copy_page = jax.jit(copy_kv_page)
                self._copy_state = jax.jit(copy_state_slot)
            else:
                # Page-granular device copy for COW splits and defrag
                # moves: every cache leaf — K/V pools and any int8 scale
                # pools — has the page axis at position 2 (n_sb, me,
                # pages, ...), so one tree.map moves a page across all
                # layers and pools at once.  src/dst are traced scalars:
                # one compilation serves every copy.
                self._copy_page = jax.jit(copy_fn)
        if use_graph:
            # Graph-compiled chunked prefill AND decode tick: each traced
            # once at the engine's fixed shapes — (B=1, T=chunk) and
            # (B=slots, T=1) — fused, executed cluster-at-a-time with a
            # compile cache (repro.graph.compiler).  ``graph_impl=None``
            # auto-selects: "pallas" on TPU (epilogue clusters dispatch to
            # the fused kernel variants), "xla" elsewhere.
            from ..graph.compiler import (compile_decode_step,
                                          compile_prefill_step)
            # Scope the compile under this engine's tune cache so the
            # cost model's whole-graph schedules persist next to the tuned
            # kernel tiles — a restarted engine replays its clustering from
            # the cache by graph signature instead of re-deriving it
            # (repro.cost.schedule).
            with scoped_cache(self.tune_cache):
                self._prefill = compile_prefill_step(
                    bundle, params, self.cache, chunk=prefill_chunk,
                    table_width=self._table_width(prefill_chunk), pctx=pctx,
                    impl=graph_impl, cost_model=graph_cost_model)
                self._decode_step = compile_decode_step(
                    bundle, params, self.cache, slots=slots,
                    table_width=self._table_width(1), pctx=pctx,
                    impl=graph_impl, cost_model=graph_cost_model)
        else:
            # same jit fn for all three entry points; shapes differ
            # (prefill: B=1 T=chunk; decode tick: B=slots T=1)
            self._prefill = self._decode
            self._decode_step = self._decode

    def _table_width(self, t: int) -> int:
        """Combined block-table width for a forward over T=``t`` positions:
        the KV page columns (always present — the allocator ledgers every
        family's tokens) plus, on state engines, one state-read column and
        ``t`` per-token state-write columns (repro.models.paged_state)."""
        width = self.kv.max_pages_per_slot
        if self.state is not None:
            width += 1 + t
        return width

    def _tables(self, rows, write_ids=None) -> np.ndarray:
        """Block-table rows for a forward call over engine slots ``rows``:
        the KV page table, extended on state engines with each row's state
        read id and the caller-built ``(len(rows), T)`` write-id columns
        (``TRASH_STATE`` for padded/inactive positions)."""
        kv_rows = self.kv.block_tables[list(rows)]
        if self.state is None:
            return kv_rows
        reads = np.array([[self.state.read_id(s)] for s in rows], np.int32)
        return np.concatenate(
            [kv_rows, reads, np.asarray(write_ids, np.int32)], axis=1)

    def _decode_kernel_shapes(self):
        """Kernel shapes the paged decode path exercises on real hardware:
        paged decode attention over the slot batch (attention families
        only) and the slot-batch GEMM.  An int8-KV engine tunes the
        ``_kvint8`` variant of the paged family — the key the int8
        gather-dequant kernel actually resolves.  On a TP mesh the
        per-shard (local) geometry is what each device runs."""
        cfg = (self.tp_plan.local_cfg if self.tp_plan is not None
               else self.bundle.cfg)
        shapes = []
        if not cfg.is_attention_free:
            attn_shape = {"b": self.slots, "hq": cfg.num_heads,
                          "hkv": cfg.num_kv_heads,
                          "d": cfg.resolved_head_dim,
                          "pages": self.kv.max_pages_per_slot,
                          "ps": self.page_size}
            if self.kv_dtype == "int8":
                attn_shape["kv_int8"] = 1
            shapes.append(("flash_decode_paged", attn_shape))
        shapes.append(("apr_matmul", {"m": self.slots, "k": cfg.d_model,
                                      "n": cfg.d_ff or cfg.d_inner}))
        return shapes

    def graph_schedule_report(self) -> str:
        """Human-readable cost-model schedule report for the graph-compiled
        steps (``launch.serve --explain``): one
        :meth:`~repro.cost.ScheduleDecision.report` block per compiled step.
        Empty when ``use_graph=False`` or the cost model was off."""
        blocks = []
        for label, step in (("prefill", self._prefill),
                            ("decode", self._decode_step)):
            ex = getattr(step, "executor", None)
            decision = getattr(ex, "schedule", None)
            if decision is not None:
                blocks.append(f"[{label}] {decision.report()}")
        return "\n".join(blocks)

    def kv_pool_bytes(self) -> int:
        """*Logical* bytes held by the device cache pools — KV pages,
        recurrent-state pools, and any int8 scale pools — the footprint
        ``kv_dtype="int8"`` / ``state_dtype="int8"`` shrink.
        On a TP mesh this is the global pool; see
        :meth:`kv_pool_bytes_per_device` for what one device holds."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(self.cache))

    def kv_pool_bytes_per_device(self) -> int:
        """Physical KV-pool bytes on the busiest device: ~global/N on a TP
        mesh with sharded KV heads (the BENCH_parallel gate), equal to
        :meth:`kv_pool_bytes` on one device or with replicated KV."""
        from ..parallel.tp import per_device_bytes
        return per_device_bytes(self.cache)

    def weight_bytes_per_device(self) -> int:
        """Physical parameter bytes on the busiest device."""
        from ..parallel.tp import per_device_bytes
        return per_device_bytes(self.params)

    # -- public API -------------------------------------------------------
    def submit(self, req: Request) -> None:
        if not req.prompt:
            raise ValueError(
                f"request {req.rid}: empty prompt (generation needs at "
                "least one conditioning token, e.g. a BOS id)")
        need = len(req.prompt) + req.max_new_tokens
        cap = min(self.kv.max_tokens_per_slot(),
                  self.kv.num_pages * self.page_size)
        if need > cap:
            raise ValueError(
                f"request {req.rid}: prompt+max_new = {need} tokens exceeds "
                f"per-request capacity {cap} (pages are exhausted even with "
                "every other request preempted)")
        self.sched.submit(req)

    def step(self) -> int:
        """One engine tick: admit, chunked prefill (token-budgeted), one
        batched decode for all DECODING slots.  Returns active requests.
        The whole tick runs under this engine's tuned-config scope, so a
        subclass tick phase (speculative verify) resolves through it too."""
        with scoped_cache(self.tune_cache):
            self._admit()
            self._prefill_tick()
            self._decode_tick()
        self.metrics.ticks += 1
        self.metrics.util_samples.append(self.kv.utilization())
        return sum(r is not None for r in self.active)

    def run_until_drained(self, max_ticks: int = 10_000) -> EngineMetrics:
        for _ in range(max_ticks):
            n = self.step()
            if n == 0 and not self.sched.waiting:
                break
        return self.metrics

    # -- tick phases ------------------------------------------------------
    def _active_requests(self) -> List[Request]:
        return [r for r in self.active if r is not None]

    def _admit(self) -> None:
        # Gate on available pages (free + lazily-evictable prefix cache) so
        # a freshly-preempted request is not bounced straight back into the
        # pool that just evicted it.
        if self.kv.available_pages == 0:
            return
        free = [i for i, r in enumerate(self.active) if r is None]
        for slot, req in self.sched.admit(free):
            self.active[slot] = req
            toks = req.prefill_tokens()
            self.metrics.prompt_pages_logical += self.kv.pages_for(len(toks))
            if self.prefix_sharing:
                # Attach the longest cached prefix; prefill resumes after
                # it (the matched tokens' KV is reused, not recomputed).
                matched = self.kv.match_prefix(slot, toks)
                if matched:
                    req.prefill_pos = matched
                    self.metrics.prefix_hit_tokens += matched
                    self.metrics.prefix_hit_requests += 1
            if self.state is not None:
                self.state.alloc(slot)
            self._on_admit(slot, req)

    def _on_admit(self, slot: int, req: Request) -> None:
        """Placement hook for subclasses (the speculative engine notifies
        its draft proposer here); the base engine needs nothing."""

    def _preempt(self, req: Request) -> None:
        self.kv.free_slot(req.slot)
        if self.state is not None:
            self.state.free_slot(req.slot)
        self.active[req.slot] = None
        self.sched.requeue_preempted(req)
        self.metrics.preemptions += 1

    def _ensure_pages(self, req: Request, n_tokens: int) -> bool:
        """Grow ``req``'s slot to hold ``n_tokens``, preempting the youngest
        active request (possibly ``req`` itself) until the pool covers it.
        Returns False if ``req`` was preempted or nothing could be freed."""
        while True:
            try:
                self.kv.allocate(req.slot, n_tokens)
                return True
            except OutOfPages:
                victim = self.sched.preemption_victim(self._active_requests())
                if victim is None:
                    return False
                self._preempt(victim)
                if victim is req:
                    return False

    def _sync_page_copies(self) -> None:
        """Mirror queued COW page splits onto the device pools.  Must run
        after any host-side ``allocate`` and before the next forward — the
        forward's writes land in the slot's *new* private page, which needs
        the shared page's prefix content under the write offset."""
        for src, dst in self.kv.pop_page_copies():
            self.cache = self._copy_page(self.cache, jnp.int32(src),
                                         jnp.int32(dst))
            self.metrics.cow_copies += 1

    def _sync_state_copies(self) -> None:
        """Mirror queued state-slot copies (truncate restores, snapshot
        materialisations, defrag moves) onto the device state pools, in
        queue order — StateCache sequences them so earlier copies always
        see the layout they were queued against."""
        if self.state is None:
            return
        for src, dst in self.state.pop_state_copies():
            self.cache = self._copy_state(self.cache, jnp.int32(src),
                                          jnp.int32(dst))

    def _truncate_slot(self, slot: int, n_tokens: int) -> None:
        """Roll one engine slot back (or commit it forward) to ``n_tokens``
        atomically across both residency domains: the KV page suffix is
        dropped AND the paired recurrent state is restored from its ring
        checkpoint before any later forward can read either."""
        self.kv.truncate(slot, n_tokens)
        if self.state is not None:
            self.state.truncate(slot, n_tokens)
            self._sync_state_copies()

    def defrag(self) -> int:
        """Compact the page pool — and on state engines the state-slot
        pool — host tables + device pools in lockstep, preserving prefix
        sharing; returns the total number of device moves."""
        moves = self.kv.defrag()
        for src, dst in moves:
            self.cache = self._copy_page(self.cache, jnp.int32(src),
                                         jnp.int32(dst))
        n = len(moves)
        if self.state is not None:
            n += len(self.state.defrag())
            self._sync_state_copies()
        return n

    def _net_unique_pages(self) -> int:
        """Physical prompt pages consumed so far, net of sharing: fresh
        allocations plus COW splits, minus pages retired by retro-dedup."""
        s = self.kv.stats
        return s["fresh_pages"] + s["cow_splits"] - s["dedup_reclaimed"]

    def _prefill_tick(self) -> None:
        prefilling = [r for r in self._active_requests()
                      if r.state == PREFILLING]
        unique0 = self._net_unique_pages()
        for req, n in self.sched.prefill_plan(prefilling):
            if self.active[req.slot] is not req:
                continue  # preempted earlier this tick by a sibling's alloc
            toks_all = req.prefill_tokens()
            if not self._ensure_pages(req, req.prefill_pos + n):
                continue
            self._sync_page_copies()
            chunk = toks_all[req.prefill_pos:req.prefill_pos + n]
            padded = chunk + [0] * (self.prefill_chunk - n)
            if self.state is not None:
                # only the chunk's last real token needs its state kept —
                # the forward carries state across tokens in registers, so
                # intermediate (and padded) positions write to the sink
                write_ids = np.full((1, self.prefill_chunk), TRASH_STATE,
                                    np.int32)
                write_ids[0, n - 1] = self.state.cur(req.slot)
            else:
                write_ids = None
            t0 = time.perf_counter()
            logits, self.cache = self._prefill(
                self.params, self.cache,
                jnp.asarray([padded], jnp.int32),
                jnp.asarray([req.prefill_pos], jnp.int32),
                jnp.asarray([n], jnp.int32),
                jnp.asarray(self._tables([req.slot], write_ids)))
            jax.block_until_ready(logits)
            self.metrics.prefill_time_s += time.perf_counter() - t0
            req.prefill_pos += n
            self.kv.commit(req.slot, req.prefill_pos)
            if self.state is not None:
                self.state.commit(req.slot, req.prefill_pos)
            if self.prefix_sharing:
                # publish completed pages so siblings (and later waves)
                # can share them; identical pages prefix-filled in parallel
                # retro-dedup onto one physical copy here
                self.kv.register_prefix(req.slot, toks_all)
            self.metrics.prefill_tokens += n
            if req.prefill_pos == len(toks_all):
                # prompt (+ recompute suffix) fully cached: the last real
                # row of this chunk's logits is the next-token distribution
                nxt = int(jnp.argmax(logits[0, n - 1]))
                if not req.first_token_at:
                    req.first_token_at = time.perf_counter()
                    self.metrics.ttfts.append(req.ttft)
                req.output.append(nxt)
                self.last_tokens[req.slot] = nxt
                req.state = DECODING
                self._maybe_finish(req, nxt)
        self.metrics.prompt_pages_unique += (self._net_unique_pages()
                                             - unique0)

    def _decode_tick(self) -> None:
        # oldest first, so page pressure evicts the youngest (LIFO) and the
        # head of the FIFO line always makes progress
        decoding = sorted(
            (r for r in self._active_requests() if r.state == DECODING),
            key=lambda r: r.admit_seq)
        for req in decoding:
            self._ensure_pages(req, self.kv.length(req.slot) + 1)
        self._sync_page_copies()
        decoding = [r for r in self._active_requests() if r.state == DECODING]
        if not decoding:
            return
        lengths = np.array([self.kv.length(s) for s in range(self.slots)],
                           np.int32)
        counts = np.zeros((self.slots,), np.int32)
        for r in decoding:
            counts[r.slot] = 1
        if self.state is not None:
            write_ids = np.full((self.slots, 1), TRASH_STATE, np.int32)
            for r in decoding:
                write_ids[r.slot, 0] = self.state.cur(r.slot)
        else:
            write_ids = None
        t0 = time.perf_counter()
        logits, self.cache = self._decode_step(
            self.params, self.cache,
            jnp.asarray(self.last_tokens[:, None], jnp.int32),
            jnp.asarray(lengths), jnp.asarray(counts),
            jnp.asarray(self._tables(range(self.slots), write_ids)))
        jax.block_until_ready(logits)
        self.metrics.decode_time_s += time.perf_counter() - t0
        next_tokens = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for req in decoding:
            self.kv.commit(req.slot, self.kv.length(req.slot) + 1)
            if self.state is not None:
                self.state.commit(req.slot, self.kv.length(req.slot))
            tok = int(next_tokens[req.slot])
            req.output.append(tok)
            self.last_tokens[req.slot] = tok
            self.metrics.decode_tokens += 1
            self._maybe_finish(req, tok)

    def _maybe_finish(self, req: Request, tok: int) -> None:
        if (req.eos_id is not None and tok == req.eos_id) or \
           len(req.output) >= req.max_new_tokens:
            self._finish(req)

    def _finish(self, req: Request) -> None:
        # allocator-level rfsmac.s: the request's accumulated KV working set
        # (and its state slot + checkpoints) flushes back to the pool in
        # one step
        self.kv.free_slot(req.slot)
        if self.state is not None:
            self.state.free_slot(req.slot)
        self.active[req.slot] = None
        req.state = DONE
        req.done = True
        req.finished_at = time.perf_counter()
        self.metrics.requests_done += 1


class ServeEngine:
    """Contiguous slot engine (seed baseline): one ``slots x max_seq`` KV
    cache, prefill-on-admit *through the decode path* token by token.

    Kept as (a) the numerics reference the paged engine must match
    token-for-token, and (b) the serving path for model families whose
    decode state is not a growing KV sequence (ssm/hybrid/audio).  For
    dense/moe/vlm traffic use :class:`PagedServeEngine`.
    """

    def __init__(self, bundle: ModelBundle, params, pctx: ParallelContext,
                 *, slots: int = 4, max_seq: int = 256,
                 tune_cache: Optional[str] = None,
                 autotune_at_start: bool = False):
        self.bundle = bundle
        self.params = params
        self.pctx = pctx
        self.slots = slots
        self.max_seq = max_seq
        # Tuned-kernel plumbing (repro.bench): an explicit ``tune_cache``
        # is scoped to THIS engine — warm-up here plus every step() — via
        # :func:`repro.bench.config.scoped_cache`, so two engines with
        # different tuned profiles (e.g. different dtypes) coexist; see
        # tests/test_autotune.py::test_two_engine_tune_caches_coexist.
        # ``autotune_at_start=True`` additionally sweeps any shape missing
        # from the cache (slow; meant for a one-off warm-up run).
        self.tune_cache = (ConfigCache(tune_cache)
                          if tune_cache is not None else None)
        with scoped_cache(self.tune_cache):
            self.tuned_configs = warm_cache(
                self._decode_kernel_shapes(), sweep=autotune_at_start)
        self.cache = bundle.init_cache(slots, max_seq)
        self.lengths = jnp.zeros((slots,), jnp.int32)
        self.active: List[Optional[Request]] = [None] * slots
        self.pending: "queue.Queue[Request]" = queue.Queue()
        self._decode = jax.jit(
            lambda p, c, t, l: bundle.decode_step(p, c, t, l, pctx)
        )
        self.last_tokens = jnp.zeros((slots, 1), jnp.int32)

    def _decode_kernel_shapes(self):
        """Kernel shapes this engine's decode path exercises: batched decode
        attention over the full slot batch, and the slot-batch x d_ff GEMM."""
        cfg = self.bundle.cfg
        return [
            ("flash_decode", {"b": self.slots, "hq": cfg.num_heads,
                              "hkv": cfg.num_kv_heads,
                              "d": cfg.resolved_head_dim,
                              "s": self.max_seq}),
            ("apr_matmul", {"m": self.slots, "k": cfg.d_model,
                            "n": cfg.d_ff}),
        ]

    def submit(self, req: Request):
        if not req.prompt:
            raise ValueError(
                f"request {req.rid}: empty prompt (generation needs at "
                "least one conditioning token, e.g. a BOS id)")
        self.pending.put(req)

    def _admit(self):
        stateful = self.bundle.cfg.family in ("ssm", "mamba", "hybrid")
        for slot in range(self.slots):
            if self.active[slot] is not None or self.pending.empty():
                continue
            req = self.pending.get()
            # prefill by decoding the prompt token-by-token into this slot
            # (keeps cache layouts identical; PagedServeEngine runs chunked
            # prefill over the paged cache contract instead).  Reset the
            # slot's length first: a reused slot must not attend over the
            # previous request's KV (stale entries beyond the new length are
            # masked, and get overwritten as the new request grows).
            if stateful:
                # Recurrent state is a running summary, not masked by
                # lengths: (a) a reused slot must start from the zero
                # state, and (b) the full-batch prompt decode below
                # advances EVERY row's recurrence, so the other slots'
                # rows are pinned across the loop (batch rows are
                # independent in decode, so restoring them once at the
                # end is exact).  Every state leaf has batch axis 1.
                keep = self.cache
                self.cache = jax.tree.map(
                    lambda a: a.at[:, slot].set(
                        jnp.zeros_like(a[:, slot])), self.cache)
            lengths = self.lengths.at[slot].set(0)
            for tok in req.prompt:
                toks = self.last_tokens.at[slot, 0].set(tok)
                logits, self.cache = self._decode(
                    self.params, self.cache, toks, lengths)
                lengths = lengths.at[slot].add(1)
            if stateful:
                self.cache = jax.tree.map(
                    lambda k, n: k.at[:, slot].set(n[:, slot]),
                    keep, self.cache)
            self.lengths = lengths
            nxt = int(jnp.argmax(logits[slot, -1]))
            if not req.first_token_at:
                req.first_token_at = time.perf_counter()
            req.output.append(nxt)
            self.last_tokens = self.last_tokens.at[slot, 0].set(nxt)
            self.active[slot] = req

    def step(self) -> int:
        """One engine tick: admit new requests, one decode for all active
        slots.  Returns number of active requests."""
        with scoped_cache(self.tune_cache):
            return self._step_inner()

    def _step_inner(self) -> int:
        self._admit()
        if not any(r is not None for r in self.active):
            return 0
        logits, self.cache = self._decode(
            self.params, self.cache, self.last_tokens, self.lengths)
        next_tokens = jnp.argmax(logits[:, -1], axis=-1)
        new_last = self.last_tokens
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            self.lengths = self.lengths.at[slot].add(1)
            tok = int(next_tokens[slot])
            req.output.append(tok)
            new_last = new_last.at[slot, 0].set(tok)
            if (req.eos_id is not None and tok == req.eos_id) or \
               len(req.output) >= req.max_new_tokens or \
               int(self.lengths[slot]) >= self.max_seq - 1:
                req.done = True
                req.finished_at = time.perf_counter()
                self.active[slot] = None
        self.last_tokens = new_last
        return sum(r is not None for r in self.active)

    def run_until_drained(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            n = self.step()
            if n == 0 and self.pending.empty():
                return
