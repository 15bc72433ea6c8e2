"""Steer the harness to a model's smoke config and a few seconds of
light traffic, so a whole run fits a CPU test."""
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def smoke_sizes(cell):
    from repro.configs import get_config

    cfg = get_config(cell.config["model"], smoke=True)
    return cfg, {k: getattr(cfg, k) for k in cell.config["sizes"]}


def shrink(cell):
    """The cell's traffic at CPU scale: 4 slots, short prompts and outputs,
    one second of warm-up."""
    t = dict(cell.traffic)
    eng = dict(t["engine"], slots=4)
    eng["num_pages"] = 4 * eng["max_pages_per_slot"]
    t["engine"] = eng
    if t["loop"] == "open":
        t["rate_per_s"] = 6.0
    else:
        t["clients"] = 4
    t["warmup_s"] = 1.0
    t["prompt"] = {"dist": "uniform", "min": 8, "max": 40}
    t["output"] = {"dist": "uniform", "min": 6, "max": 14}
    t["check_tokens"] = 24
    cell.traffic = t
    return cell


def smoke_cell(name, monkeypatch, root=ROOT):
    from chipbench import cell as cellmod
    from chipbench import harness

    monkeypatch.setattr(harness, "model_sizes", smoke_sizes)
    return shrink(cellmod.load(root, name))
