"""A run whose timed path is broken underneath comes out not correct.

Each test skips the look for a chip, steers the harness to the model's
smoke config (``_smoke``) and drives a whole run on the CPU with one fault
planted in the program: a token altered where it is produced, a step that
returns its cache unchanged, half of the decode batch left out (its rows
given the other half's results).  The comparison with the reference has
to catch each.
"""
import io

import pytest

from chipbench import harness
from _smoke import smoke_cell

SEED = 2**31 + 77


def _run(name, monkeypatch):
    cell = smoke_cell(name, monkeypatch)
    cell.traffic["check_tokens"] = 10**6    # every finished window request
    return harness.run_cell(cell, SEED, 1.5, False, 0.0, out=io.StringIO())


def _alter_token(monkeypatch):
    from repro.serve import engine as E

    orig = E.PagedServeEngine._maybe_finish

    def altered(self, req, tok):
        if len(req.output) == 3:
            req.output[-1] = (tok + 1) % self.bundle.cfg.vocab_size
            self.last_tokens[req.slot] = req.output[-1]
        return orig(self, req, req.output[-1])

    monkeypatch.setattr(E.PagedServeEngine, "_maybe_finish", altered)


def _stale_cache(monkeypatch):
    from repro.models import registry

    orig = registry.ModelBundle.decode_paged

    def stale(self, params, cache, *a, **k):
        logits, _ = orig(self, params, cache, *a, **k)
        return logits, cache

    monkeypatch.setattr(registry.ModelBundle, "decode_paged", stale)


def _half_batch(monkeypatch):
    from repro.models import registry

    orig = registry.ModelBundle.decode_paged

    def half(self, params, cache, *a, **k):
        logits, cache = orig(self, params, cache, *a, **k)
        if logits.shape[0] > 1:   # the decode step: odd rows reuse even ones
            logits = logits.at[1::2].set(logits[0::2])
        return logits, cache

    monkeypatch.setattr(registry.ModelBundle, "decode_paged", half)


@pytest.mark.parametrize("fault", [_alter_token, _stale_cache, _half_batch],
                         ids=["token_altered", "cache_unchanged", "half_batch"])
@pytest.mark.parametrize("name", ["chatglm3-6b.chat"])
def test_fault_makes_run_incorrect(name, fault, monkeypatch):
    fault(monkeypatch)
    result = _run(name, monkeypatch)
    gap = result["checks"]["mean_logit_gap"]
    assert result["correct"] is False
    assert gap["value"] > gap["limit"]


def test_sound_run_is_correct(monkeypatch):
    result = _run("chatglm3-6b.chat", monkeypatch)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
