"""One run of one cell: build, warm up, drive the traffic, check, report.

The program under test is entered only through its public serving path:
``repro.models.build_model`` for the model's code,
``repro.serve.PagedServeEngine`` with the cell's settings, and
``repro.serve.AsyncServeFrontend`` for every request of the run.  The
weights, the traffic, the spans, the reference and the metrics are the
benchmark's own.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import math
import shutil
import sys
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

from . import cell as cellmod
from . import reference, spans, trace as tracemod, traffic, weights, window
from .peaks import peaks_for
from .window import RequestRecord, Window

#: how long past the window's close its requests may take to answer
DRAIN_S = 60.0
#: where a traced run keeps its profile (fixed, inside the checkout)
TRACE_DIR = ".chipbench/trace"


def model_sizes(cell: cellmod.Cell):
    """The program's config for the cell's model, held to the sizes its
    configuration file states."""
    from repro.configs import get_config

    cfg = get_config(cell.config["model"])
    sizes = cell.config["sizes"]
    wrong = {k: (v, getattr(cfg, k, None)) for k, v in sizes.items()
             if getattr(cfg, k, None) != v}
    if wrong:
        raise SystemExit(f"{cell.config['model']}: the program's config "
                         f"differs from the configuration file: {wrong}")
    return cfg, sizes


@dataclasses.dataclass
class Run:
    """What the metric readers read (``metrics/<name>.py``)."""
    cell: cellmod.Cell
    family: object
    sizes: dict
    peaks: dict
    window: Window
    records: List[RequestRecord]
    calls: List[spans.Call]
    setup_s: float
    trace: Optional[tracemod.Trace] = None

    def calls_in_window(self, kind: str) -> List[spans.Call]:
        return [c for c in self.calls
                if c.kind == kind and self.window.holds(c.t0)]


def build(cell: cellmod.Cell, cfg, sizes: dict, fam, seed: int):
    """Model code, weights from the seed and the engine with the cell's
    settings; returns ``(engine, seconds to make the weights)``."""
    import jax
    from repro.models import build_model
    from repro.parallel.sharding import ParallelContext
    from repro.serve import PagedServeEngine

    bundle = build_model(cfg)
    have = {k: tuple(v.shape) for k, v in bundle.abstract_params().items()}
    want = fam.param_specs(sizes)
    if have != want:
        raise SystemExit(f"the program's parameters differ from the "
                         f"{cell.config['family']} family's layout: "
                         f"{sorted(set(have.items()) ^ set(want.items()))}")
    t0 = time.perf_counter()
    params = weights.params_from_seed(want, fam, seed)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    engine = PagedServeEngine(bundle, params, ParallelContext(None),
                              **cell.traffic["engine"])
    return engine, init_s


def warm_up(engine) -> None:
    """Run the cell's two programs once: a prefill chunk ``(1, chunk)`` (a
    full one and a partial one) and the decode step ``(slots, 1)``."""
    from repro.serve import Request
    from repro.serve.engine import EngineMetrics

    req = Request(rid=-1, prompt=[2] * (engine.prefill_chunk + 1),
                  max_new_tokens=2)
    engine.submit(req)
    engine.run_until_drained()
    if not req.done:
        raise RuntimeError("warm-up request did not finish")
    engine.metrics = EngineMetrics()


async def drive(engine, cell: cellmod.Cell, seed: int, seconds: float,
                vocab: int, trace_dir: Optional[Path],
                drain_s: Optional[float] = None):
    """Offer the cell's traffic through the front end; returns the records
    and the window.  A traced run profiles the window's last ``trace_s``."""
    from repro.serve import AsyncServeFrontend

    tr = cell.traffic
    records: List[RequestRecord] = []
    consumers = []
    stopping = False
    front = AsyncServeFrontend(engine)
    await front.start()
    if tr["loop"] == "open":
        specs = traffic.open_loop(tr, seconds)
        prompts = {s.index: traffic.prompt_tokens(seed, s.index, s.prompt_len,
                                                  vocab) for s in specs}
    else:
        pool = iter(traffic.closed_pool(tr, seed))
    t_base = time.perf_counter()
    w = Window(t_base + tr["warmup_s"], t_base + tr["warmup_s"] + seconds)

    async def consume(stream, rec):
        async for tok in stream:
            rec.token_times.append(time.perf_counter())
            rec.tokens.append(tok)

    async def send(spec, due, prompt):
        rec = RequestRecord(spec.index, due, spec.prompt_len, spec.max_new)
        records.append(rec)
        try:
            stream = await front.submit(prompt, max_new_tokens=spec.max_new)
        except ValueError as e:    # the engine refused it: a failure
            rec.error = repr(e)
            return None
        rec.submitted = time.perf_counter()
        return stream, rec

    async def open_gen():
        for spec in specs:
            due = t_base + spec.due_s
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            if stopping:
                return
            sent = await send(spec, due, prompts[spec.index])
            if sent:
                consumers.append(asyncio.ensure_future(consume(*sent)))

    async def client(start):
        await asyncio.sleep(max(0.0, t_base + start - time.perf_counter()))
        while time.perf_counter() < w.close and not stopping:
            spec = next(pool)
            sent = await send(spec, time.perf_counter(), traffic.prompt_tokens(
                seed, spec.index, spec.prompt_len, vocab))
            if sent is None:
                continue
            await consume(*sent)
            if not sent[1].finished:
                return

    gens = ([asyncio.ensure_future(open_gen())] if tr["loop"] == "open" else
            [asyncio.ensure_future(client(s)) for s in traffic.client_starts(tr)])
    if trace_dir is not None:
        import jax

        await asyncio.sleep(max(0.0, w.close - tr["trace_s"]
                                - time.perf_counter()))
        jax.profiler.start_trace(str(trace_dir))
    await asyncio.sleep(max(0.0, w.close - time.perf_counter()))
    if trace_dir is not None:
        jax.profiler.stop_trace()
    # every request due in the window is sent and has its first token
    n_due = (sum(1 for s in specs if w.holds(t_base + s.due_s))
             if tr["loop"] == "open" else None)
    deadline = w.close + (DRAIN_S if drain_s is None else drain_s)

    def pending():
        due = window.due_in(records, w)
        return ((n_due is not None and len(due) < n_due)
                or any(r.error is None and not r.token_times for r in due))

    while time.perf_counter() < deadline and pending():
        await asyncio.sleep(0.02)
    stopping = True
    for g in gens:
        g.cancel()
    await front.stop()
    for res in await asyncio.gather(*gens, *consumers,
                                    return_exceptions=True):
        if (isinstance(res, BaseException)
                and not isinstance(res, asyncio.CancelledError)):
            raise res
    return records, w


def pick_sample(records: List[RequestRecord], w: Window, seed: int,
                min_tokens: int) -> List[RequestRecord]:
    """Finished window requests for the reference: the longest, the one
    with the most output, then others in an order drawn from the seed,
    until they hold ``min_tokens`` served tokens."""
    done = [r for r in window.due_in(records, w) if r.finished]
    if not done:
        return []
    rng = np.random.default_rng([seed, 0x5A])
    order = [max(done, key=lambda r: r.prompt_len + r.max_new),
             max(done, key=lambda r: r.max_new)]
    order += [done[i] for i in rng.permutation(len(done))]
    out, seen, n = [], set(), 0
    for r in order:
        if r.index in seen:
            continue
        out.append(r)
        seen.add(r.index)
        n += len(r.tokens)
        if n >= min_tokens and len(out) >= 2:
            break
    return out


def judge(gaps: np.ndarray, limits: dict, unanswered: int):
    """The numbers compared, each beside its limit, and whether all hold:
    the mean over served tokens of the gap by which a served token's
    reference logit lies below the reference's best (limit from the
    cell's limits file), and the window's requests that never answered
    (limit 0).  No gaps at all is not correct."""
    checks = {
        "mean_logit_gap": {
            "value": float(np.mean(gaps)) if gaps.size else math.inf,
            "limit": limits["mean_logit_gap"]},
        "unanswered_requests": {"value": unanswered, "limit": 0},
    }
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def gap_arrays(fam, sizes: dict, seed: int, sample: List[RequestRecord],
               vocab: int, control: bool = False):
    """Per served token of the sample, the gap of the served token (and
    of the int8 control's first token) below the reference's best."""
    prompts = [traffic.prompt_tokens(seed, r.index, r.prompt_len, vocab)
               for r in sample]
    return reference.gaps(fam, sizes, seed, prompts,
                          [r.tokens[:r.max_new] for r in sample], control)


def run_cell(cell: cellmod.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, out=sys.stdout) -> dict:
    """One run; prints the set-up breakdown and the checks, and returns
    the result line's object."""
    import jax

    meter = spans.CompileMeter()
    dev = jax.devices()[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    fam = cellmod.family(cell)
    cfg, sizes = model_sizes(cell)
    engine, init_s = build(cell, cfg, sizes, fam, seed)
    rec = spans.Recorder(engine, annotate=trace)
    t0 = time.perf_counter()
    warm_up(engine)
    warm_s = time.perf_counter() - t0
    rec.reset()
    trace_dir = None
    if trace:
        trace_dir = cell.root / TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    compile_s, hits = meter.seconds, meter.cache_hits
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    records, w = asyncio.run(
        drive(engine, cell, seed, seconds, sizes["vocab_size"], trace_dir))
    in_window = meter.between(w.open, w.close)
    peak = spans.peak_bytes(dev)
    for c in rec.calls:               # read the call arguments back now
        if c.kind != spans.STEP:
            c.lengths, c.counts = np.asarray(c.lengths), np.asarray(c.counts)
    del engine                        # free the program's weights and cache
    gc.unfreeze()
    gc.collect()
    run = Run(cell, fam, sizes, peaks, w, records, rec.calls, setup_s)
    if trace:
        run.trace = tracemod.load(str(trace_dir))
    due = window.due_in(records, w)
    bad = window.failed(records, w)
    sample = pick_sample(records, w, seed, cell.traffic["check_tokens"])
    t0 = time.perf_counter()
    gaps = (gap_arrays(fam, sizes, seed, sample, sizes["vocab_size"])[0]
            if sample else np.zeros(0))
    ref_s = time.perf_counter() - t0
    checks, correct = judge(gaps, cell.limits, len(bad))
    lags = window.submit_lags(records, w)
    tt = window.ttfts(records, w)
    info = {
        "setup": {"setup_s": setup_s, "weights_s": init_s, "warm_up_s": warm_s,
                  "compile_s": compile_s, "compile_cache_hits": hits},
        "window": {"seconds": w.seconds, "requests_due": len(due),
                   "finished": sum(r.finished for r in due),
                   "failed": len(bad),
                   "compiles_in_window": in_window,
                   "generator_late_p50_ms": 1e3 * window.percentile(lags, 50)
                   if lags else None,
                   "generator_late_max_ms": 1e3 * max(lags) if lags else None,
                   "ttft_p50_ms": 1e3 * window.percentile(tt, 50) if tt else None,
                   "ttft_p90_ms": 1e3 * window.percentile(tt, 90) if tt else None},
        "memory_peak_bytes": peak,
        "check": {"requests": len(sample), "served_tokens": int(gaps.size),
                  "reference_s": ref_s,
                  "max_logit_gap": float(np.max(gaps)) if gaps.size else None},
    }
    print("chipbench-info " + _dumps(info), file=out, flush=True)
    chosen = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in chosen:
        v = cellmod.reader(cell.root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(due), "failed": len(bad),
              "metrics": metrics, "device": device}
    if trace:
        w0, w1 = run.trace.window()
        device["busy_s"] = tracemod.busy(run.trace, [(w0, w1)]) / 1e9
        device["window_s"] = (w1 - w0) / 1e9
        result["breakdown"] = tracemod.breakdown(run.trace)
    result["checks"] = checks
    meter.stop()
    for k, v in checks.items():
        print(f"check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    return result


def _dumps(obj) -> str:
    import json

    return json.dumps(obj, default=float)
