"""The control: the reference computed in int8 (one step below the bf16
the configurations state) reads wider logit gaps than the program does,
over the same served requests, on a few seeds (here at the smoke
config), and at the cell's own size the run's own comparison
(``harness.judge``) with the committed limit finds it not correct (on
gaps that ``chipbench/calibrate.py`` read on the chip).  At 64 wide,
int8 rounds too near bf16 for the published-width limit to apply."""
import asyncio
import gzip
import json

import numpy as np
import pytest

from chipbench import cell as cellmod
from chipbench import harness
from _smoke import ROOT, smoke_cell

DATA = ROOT / "tests" / "chipbench" / "data"


@pytest.mark.parametrize("name", ["chatglm3-6b.chat"])
def test_control_reads_wider_than_the_program(name, monkeypatch):
    cell = smoke_cell(name, monkeypatch)
    # enough served tokens for near ties: the widest gap is a tail
    cell.traffic["output"] = {"dist": "uniform", "min": 30, "max": 60}
    fam = cellmod.family(cell)
    cfg, sizes = harness.model_sizes(cell)
    readings = []
    for seed in (3, 4):
        engine, _ = harness.build(cell, cfg, sizes, fam, seed)
        harness.warm_up(engine)
        records, w = asyncio.run(harness.drive(
            engine, cell, seed, 3.0, sizes["vocab_size"], None))
        sample = harness.pick_sample(records, w, seed, 400)
        assert sample
        prog, ctl = harness.gap_arrays(fam, sizes, seed, sample,
                                       sizes["vocab_size"], control=True)
        assert prog.size == ctl.size == sum(r.max_new for r in sample)
        assert prog.min() >= 0 and ctl.min() >= 0
        readings.append((prog.mean(), ctl.mean()))
    # few tokens flip at this size, so the readings are pooled over seeds;
    # at 64 wide, int8 rounds nearer to bf16 than at published widths,
    # where the control's smallest reading is over three times the
    # program's largest
    assert all(ctl > gap for gap, ctl in readings), readings
    prog_mean = sum(g for g, _ in readings) / len(readings)
    ctl_mean = sum(c for _, c in readings) / len(readings)
    assert ctl_mean > 1.5 * prog_mean, readings


def test_judge_holds_each_number_to_its_limit():
    limits = {"mean_logit_gap": 0.01}
    checks, ok = harness.judge(np.array([0.0, 0.0, 0.015]), limits, 0)
    assert ok and checks["mean_logit_gap"] == {"value": 0.005, "limit": 0.01}
    assert not harness.judge(np.array([0.0, 0.03]), limits, 0)[1]
    assert not harness.judge(np.array([0.0]), limits, 1)[1]
    assert not harness.judge(np.zeros(0), limits, 0)[1]


def test_control_fails_the_committed_limit_on_chip_readings():
    """Per-token gaps read on the chip at the cell's own size and load
    (``calibrate.py --save``): through the run's own comparison and the
    cell's committed limits file, every seed's program comes out correct
    and every seed's int8 control does not."""
    with gzip.open(DATA / "chatglm3-6b.chat.gaps.json.gz", "rt") as f:
        data = json.load(f)
    cell = cellmod.load(ROOT, data["workload"])
    assert len(data["seeds"]) >= 12
    for s in data["seeds"]:
        prog, ctl = np.asarray(s["program"]), np.asarray(s["control"])
        assert prog.size == ctl.size >= cell.traffic["check_tokens"]
        assert harness.judge(prog, cell.limits, 0)[1] is True, s["seed"]
        assert harness.judge(ctl, cell.limits, 0)[1] is False, s["seed"]
