"""Speculative decoding on the paged serving engine.

:class:`SpeculativeServeEngine` replaces the one-token decode tick of
:class:`~repro.serve.engine.PagedServeEngine` with a draft-and-verify step
that attacks the same bound the APR attacks at kernel level — work per
memory pass.  Plain decode streams the target model's weights once per
generated token; a speculative step streams them once per *verify batch*:
a draft proposes ``k`` tokens, the target scores all ``k + 1`` positions
(pending token + proposals) in ONE batched ``decode_paged`` forward, and
greedy verification accepts the longest proposal prefix that matches the
target's own argmaxes, plus one bonus token from the target itself.  Every
accepted token amortises the weight stream; every rejected token costs a
host-side rollback — ``PagedKVCache.truncate`` drops the KV page suffix,
and on recurrent-state families (rwkv6/mamba2/zamba2) the paired
``StateCache`` checkpoint written by the verify forward is restored in the
same ``_truncate_slot`` call, so KV pages and state roll back atomically.

Token-identity guarantee: row ``i`` of the verify logits is computed from
exactly the state the plain engine would have after emitting the first
``i`` tokens (the paged cache holds the same KV at the same positions, the
causal-within-chunk mask exposes the same prefix), and emission stops at
the first position where the proposal disagrees with the target's argmax —
substituting the argmax itself.  Greedy outputs therefore match the plain
engine token for token, at any acceptance rate, for any proposer (an empty
proposal degrades a slot to a plain decode step).  The guarantee is gated
in CI by ``benchmarks/bench_spec.py --quick``.

Everything below the tick is inherited unchanged: pages, chunked prefill,
FIFO admission, preemption-with-recompute (the draft is notified through
its ``admit``/``release`` hooks and recovers by re-syncing), int8 KV
(``kv_dtype="int8"`` — rollback leaves stale payload+scale slots that are
masked by length and rewritten in lockstep, see ``docs/quantization.md``).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..bench.specs import spec_verify_shapes
from ..models.registry import ModelBundle, check_draft_pair
from ..parallel.sharding import ParallelContext
from ..serve.engine import PagedServeEngine
from ..serve.scheduler import DECODING, DONE, Request
from ..serve.state_cache import TRASH_STATE
from .draft import DraftProposer, ModelDraft, NgramDraft


class SpeculativeServeEngine(PagedServeEngine):
    """Draft-and-verify continuous batching over the paged KV cache.

    ``draft`` is any :class:`~repro.spec.draft.DraftProposer`; with
    ``draft=None`` an :class:`NgramDraft` self-drafting fallback is used.
    ``spec_k`` is the per-slot proposal budget (``spec_k=0`` degenerates to
    the plain engine, one verify row per slot); ``verify_budget`` caps the
    verify rows one tick may spend across slots (see
    :meth:`repro.serve.scheduler.FifoScheduler.verify_plan`).
    """

    def __init__(self, bundle: ModelBundle, params, pctx: ParallelContext,
                 *, spec_k: int = 4, draft: Optional[DraftProposer] = None,
                 draft_bundle: Optional[ModelBundle] = None,
                 draft_params=None, verify_budget: Optional[int] = None,
                 **kwargs):
        if spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        self.spec_k = spec_k  # set before super().__init__ warms kernels
        super().__init__(bundle, params, pctx, **kwargs)
        self.sched.verify_budget = verify_budget
        if draft is not None and draft_bundle is not None:
            raise ValueError("pass either draft= or draft_bundle=, not both")
        if draft_bundle is not None:
            check_draft_pair(bundle.cfg, draft_bundle.cfg)
            draft = ModelDraft(draft_bundle, draft_params, pctx,
                               slots=self.slots, page_size=self.page_size,
                               num_pages=self.kv.num_pages,
                               max_pages_per_slot=self.kv.max_pages_per_slot,
                               chunk=self.prefill_chunk,
                               kv_dtype=self.kv_dtype)
        self.draft: DraftProposer = draft if draft is not None else NgramDraft()

    def _decode_kernel_shapes(self):
        """Plain decode shapes plus the widened verify-batch GEMM (the
        verify attention reuses the already-warm paged family)."""
        return (super()._decode_kernel_shapes()
                + spec_verify_shapes(self.bundle.cfg, self.slots, self.spec_k))

    # -- draft lifecycle hooks -------------------------------------------
    def _on_admit(self, slot: int, req: Request) -> None:
        self.draft.admit(slot, req)

    def _preempt(self, req: Request) -> None:
        slot = req.slot
        super()._preempt(req)
        self.draft.release(slot)

    def _finish(self, req: Request) -> None:
        slot = req.slot
        super()._finish(req)
        self.draft.release(slot)

    # -- the speculative tick --------------------------------------------
    def _decode_tick(self) -> None:
        decoding = [r for r in self._active_requests() if r.state == DECODING]
        if not decoding:
            return
        plan = self.sched.verify_plan(decoding, self.spec_k)
        # Reserve pages for the worst case (k proposals + the pending token
        # all written) before drafting; reservation may preempt a younger
        # sibling that is itself in the plan, so re-check liveness after.
        alive: List[Tuple[Request, int]] = []
        for req, k in plan:
            if self.active[req.slot] is not req or req.state != DECODING:
                continue
            if self._ensure_pages(req, self.kv.length(req.slot) + k + 1):
                alive.append((req, k))
        alive = [(r, k) for r, k in alive
                 if self.active[r.slot] is r and r.state == DECODING]
        self._sync_page_copies()   # reservation may have COW-split a shared
                                   # boundary page (prefix sharing)
        if not alive:
            return

        with self.spans.timed("draft", rows=len(alive)) as call:
            proposals = self.draft.propose(
                [(r.slot, r, k) for r, k in alive])
        self.metrics.draft_time_s += call.seconds

        t_verify = self.spec_k + 1
        tokens = np.zeros((self.slots, t_verify), np.int32)
        counts = np.zeros((self.slots,), np.int32)
        props = {}
        lengths = np.array([self.kv.length(s) for s in range(self.slots)],
                           np.int32)
        if self.state is not None:
            # Recurrent state cannot drop a suffix: every verify position
            # writes its post-token state into a fresh ring checkpoint
            # (snapshot ids handed out empty, scattered into by the
            # forward), so the rollback below *restores* the checkpoint at
            # the accepted count instead of truncating.
            write_ids = np.full((self.slots, t_verify), TRASH_STATE,
                                np.int32)
        else:
            write_ids = None
        for req, k in alive:
            p = [int(t) for t in proposals.get(req.slot, [])[:k]]
            props[req.slot] = p
            tokens[req.slot, 0] = self.last_tokens[req.slot]
            tokens[req.slot, 1:1 + len(p)] = p
            counts[req.slot] = 1 + len(p)
            if self.state is not None:
                for t in range(1 + len(p)):
                    write_ids[req.slot, t] = self.state.snapshot(
                        req.slot, int(lengths[req.slot]) + t + 1, copy=False)
        with self.spans.timed("verify", rows=len(alive),
                              new_tokens=int(counts.sum())) as call:
            logits, self.cache = self._verify(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(lengths), jnp.asarray(counts),
                jnp.asarray(self._tables(range(self.slots), write_ids)))
            jax.block_until_ready(logits)
        self.metrics.decode_time_s += call.seconds
        self.metrics.decode_calls += 1
        greedy = np.asarray(jnp.argmax(logits, axis=-1))     # (slots, T)

        for req, _k in alive:
            slot = req.slot
            p = props[slot]
            # greedy[slot, i] is what plain decode would emit at position i;
            # accept proposals while they agree, then emit the target's own
            # token (correction on mismatch, bonus after full acceptance).
            emitted: List[int] = []
            accepted = 0
            for i, d in enumerate(p):
                g = int(greedy[slot, i])
                emitted.append(g)
                if g != d:
                    break
                accepted += 1
            else:
                emitted.append(int(greedy[slot, len(p)]))
            n_emitted = 0
            for tok in emitted:
                req.output.append(tok)
                self.last_tokens[slot] = tok
                self.metrics.decode_tokens += 1
                n_emitted += 1
                self._maybe_finish(req, tok)
                if req.state == DONE:
                    break     # later candidates are past eos/max_new
            # Acceptance is only credited for tokens that were actually
            # emitted: a proposal matching the target's argmax *past* an
            # eos/max_new stop produced nothing, and counting it would let
            # acceptance_rate disagree with tokens_per_step.
            accepted = min(accepted, n_emitted)
            self.metrics.spec_steps += 1
            req.spec_steps += 1
            self.metrics.draft_proposed += len(p)
            req.draft_proposed += len(p)
            self.metrics.draft_accepted += accepted
            req.draft_accepted += accepted
            if req.state == DONE:
                continue      # _finish freed the pages and the draft slot
            # Cache holds KV for the pending token + every *written*
            # proposal; only pending + accepted proposals are real.  The
            # last emitted token (correction/bonus) was never fed, so it is
            # the new pending token, exactly like a plain decode's output.
            # On state engines the same call atomically restores the
            # recurrent-state checkpoint at the accepted count.
            self._truncate_slot(slot, int(lengths[slot]) + 1 + accepted)
            self.draft.observe(slot, req, int(lengths[slot]) + 1 + accepted)
