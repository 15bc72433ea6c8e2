"""A configuration, a cell, a traffic mix and a metric added as new files
only: the harness finds them by name, and a whole run of the new cell
reports the new metric.  Also: the command refuses to run without a TPU."""
import io
import json
import os
import shutil
import subprocess
import sys

from chipbench import cell as cellmod
from chipbench import harness
from _smoke import ROOT, smoke_cell

NEW_CONFIG = "chatglm3-6b-copy"
NEW_CELL = f"{NEW_CONFIG}.tiny-open"
NEW_CLOSED_CELL = f"{NEW_CONFIG}.tiny-closed"
NEW_METRIC = "requests.answered"


def _checkout_with_additions(tmp_path):
    """A copy of the benchmark's files with one configuration, one traffic
    mix, one limits file, one metric reader and their BENCHMARK.json
    entries added."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = dict(bench["configs"][0], name=NEW_CONFIG,
                file=f"chipbench/configs/{NEW_CONFIG}.json")
    bench["configs"].append(conf)
    shutil.copy(ROOT / bench["configs"][0]["file"], root / conf["file"])
    bench["workloads"].append({
        "name": NEW_CELL, "config": NEW_CONFIG, "traffic": "tiny-open",
        "chips": 1, "why": "a test cell"})
    bench["end_to_end"].append({
        "name": NEW_METRIC, "unit": "requests", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": [NEW_CELL]})
    bench["workloads"].append({
        "name": NEW_CLOSED_CELL, "config": NEW_CONFIG,
        "traffic": "tiny-closed", "chips": 1, "why": "a test cell"})
    traffic = json.loads(
        (ROOT / "chipbench" / "traffic" / "chat.json").read_text())
    traffic["engine"] = dict(traffic["engine"], prefill_chunk=16,
                             prefill_budget=16)
    closed = dict(traffic, loop="closed", clients=4, client_spacing_s=0.3)
    del closed["rate_per_s"]
    limits = json.loads((ROOT / "chipbench" / "limits" /
                         "chatglm3-6b.chat.json").read_text())
    for name, mix, cell in (("tiny-open", traffic, NEW_CELL),
                            ("tiny-closed", closed, NEW_CLOSED_CELL)):
        (root / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
        (root / "chipbench" / "limits" / f"{cell}.json").write_text(
            json.dumps(limits))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chipbench" / "metrics" / f"{NEW_METRIC}.py").write_text(
        "from chipbench import window\n\n\n"
        "def read(run):\n"
        "    return sum(bool(r.token_times) for r in window.due_in(\n"
        "        run.records, run.window))\n")
    return root


def test_new_cell_and_metric_found_by_name(tmp_path, monkeypatch):
    root = _checkout_with_additions(tmp_path)
    cell = cellmod.load(root, NEW_CELL)
    assert cell.traffic["engine"]["prefill_chunk"] == 16
    assert [m["name"] for m in cell.end_to_end] == [
        "output_tok_s", "itl_p95_ms", "setup_s", NEW_METRIC]
    assert cellmod.reader(root, NEW_METRIC).__module__.startswith(
        "chipbench_metric_")
    # nothing of the harness names the new cell, traffic or metric
    assert cell.config["model"] == "chatglm3-6b"
    for path in (ROOT / "chipbench").rglob("*.py"):
        text = path.read_text()
        assert NEW_CONFIG not in text and NEW_METRIC not in text

    cell = smoke_cell(NEW_CELL, monkeypatch, root=root)
    result = harness.run_cell(cell, 2**31 + 5, 2.0, False, 0.0,
                              out=io.StringIO())
    assert result["correct"] is True, result["checks"]
    m = result["metrics"]
    assert set(m) == {"output_tok_s", "itl_p95_ms", "setup_s", NEW_METRIC}
    assert m[NEW_METRIC]["value"] == result["attempted"] > 0
    assert m[NEW_METRIC]["unit"] == "requests"
    assert result["device"]["platform"] == "cpu"


def test_new_closed_loop_cell_runs(tmp_path, monkeypatch):
    """A closed-loop mix, added as a data file, drives a whole run: its
    clients start before the window and every request they send in it
    is answered."""
    root = _checkout_with_additions(tmp_path)
    cell = smoke_cell(NEW_CLOSED_CELL, monkeypatch, root=root)
    assert cell.traffic["loop"] == "closed"
    result = harness.run_cell(cell, 2**31 + 6, 2.0, False, 0.0,
                              out=io.StringIO())
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "ttft_p90_ms" not in result["metrics"]


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "chatglm3-6b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
