"""A later change adds a cell, a mix or a metric by adding files and
entries only: the harness finds each by the name BENCHMARK.json gives."""

import json
import shutil
from pathlib import Path

from bench import run as R
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def test_added_files_are_found_by_name(tmp_path, monkeypatch, capsys):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    # a new traffic mix: data only
    mix = dict(tiny.CHAT, why="a throwaway mix")
    (tmp_path / "bench/traffic/throwaway.json").write_text(json.dumps(mix))
    # a new per-layer metric: one small reader
    (tmp_path / "bench/metrics/requests_due.throwaway.py").write_text(
        "from bench.core.readers import due_in_window\n\n\n"
        "def read(ctx):\n    return float(len(due_in_window(ctx)))\n")
    spec["workloads"].append({"name": "granite-3-8b.throwaway",
                              "config": "granite-3-8b",
                              "traffic": "throwaway", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "requests_due.throwaway",
                              "unit": "requests", "better": "higher",
                              "source": "host_clock", "layer": "front door",
                              "moves": "ttft_p90_ms",
                              "workloads": ["granite-3-8b.throwaway"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and "granite-3-8b.chat-prefix" in m["workloads"]:
            m["workloads"].append("granite-3-8b.throwaway")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(R, "ROOT", tmp_path)
    monkeypatch.setattr(R, "BENCH", tmp_path / "bench")

    c = R.load_cell("granite-3-8b.throwaway")
    assert c["mix"]["why"] == "a throwaway mix"
    assert [m["name"] for m in c["per_layer"]] == ["requests_due.throwaway"]
    assert {m["name"] for m in c["end_to_end"]} == {
        "ttft_p90_ms", "setup_s"}
    # the same cell at toy widths, traced: the new reader is called
    c.update(cfg=tiny.cell()["cfg"], peak=tiny.PEAK, out=tmp_path)
    args = R.parser().parse_args(["--workload", "granite-3-8b.throwaway",
                                  "--seed", "4", "--seconds", "2",
                                  "--trace", "1"])
    assert R.run(args, c, require_tpu=False) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["metrics"]["requests_due.throwaway"]["value"] > 0
