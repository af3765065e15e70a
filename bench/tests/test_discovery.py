"""A later change adds a cell, a mix or a metric by adding files and
entries only: the harness finds each by the name BENCHMARK.json gives."""

import hashlib
import json
import shutil
from pathlib import Path

import pytest

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


HYBRID = {
    "name": "tiny-hybrid", "model_type": "tiny_hybrid",
    "reference": "bench/reference/tiny_hybrid.py",
    "num_hidden_layers": 4, "attn_layer_period": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "vocab_size": 512,
    "num_experts": 4, "num_experts_per_tok": 2,
    "expert_intermediate_size": 64,
    "mamba_d_state": 16, "mamba_head_dim": 16, "mamba_expand": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 16,
    "tie_word_embeddings": True, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6,
    "deployment": dict(tiny.CFG["deployment"]),
    # at these widths, over eight seeds, the program read 0.015-0.072
    # and a served token altered by one id 0.77-0.96
    "check": {"min_tokens": 96, "max_seqs": 6, "max_logit_gap": 0.25},
}


def _hashes(root: Path) -> dict:
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_architecture_from_files_alone(tmp_path, monkeypatch, capsys):
    """An architecture the harness has never seen (attention every second
    layer, SSD state layers, a 4-expert top-2 MoE) joins a copy of the
    benchmark as new files and entries only: its configuration, its
    architecture module, a traffic mix and BENCHMARK.json entries.  Its
    cell runs traced and correct, its state arena warmed and freed with
    the KV arenas, its FLOPs and page bytes counted by its own module;
    and no file that was there changes."""
    from bench.core import trace
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench/configs/tiny-hybrid.json").write_text(
        json.dumps(HYBRID))
    (tmp_path / "bench/reference/tiny_hybrid.py").write_text(
        (ROOT / "bench/tests/hybrid_arch.py").read_text())
    (tmp_path / "bench/traffic/tiny-chat.json").write_text(
        json.dumps(tiny.CHAT))
    cell = "tiny-hybrid.chat"
    spec["configs"].append({"name": "tiny-hybrid", "source": "test",
                            "file": "bench/configs/tiny-hybrid.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "tiny-hybrid",
                              "traffic": "tiny-chat", "chips": 1,
                              "why": "test"})
    mine = ("ttft_p90_ms", "step_mfu.chat", "pim_page_roofline.chat")
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in mine:
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(R, "ROOT", tmp_path)
    monkeypatch.setattr(R, "BENCH", tmp_path / "bench")

    def device_stand_in(tr, top=10):
        """The CPU's profile has no device plane: a device busy through
        the whole traced window, a tenth of it in page-init programs."""
        marks = sorted(s for n, s, _ in tr["host"] if n == "bench.mark")
        w = (marks[-1] - marks[0]) * 1e-9
        return trace.Summary(window_s=w, busy_s=w, module_s={
            "jit_pim_page_init_batched": w / 10})

    monkeypatch.setattr(trace, "summarise", device_stand_in)
    c = R.load_cell(cell)
    assert [m["name"] for m in c["per_layer"]] == list(mine[1:])
    c.update(peak=tiny.PEAK, out=tmp_path)
    args = R.parser().parse_args(["--workload", cell, "--seed",
                                  str(2 ** 33 + 5), "--seconds", "3",
                                  "--trace", "1"])
    assert R.run(args, c, require_tpu=False) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True, err[-3000:]
    for m in mine[1:]:
        assert res["metrics"][m]["value"] > 0, res["metrics"]
    # the build's log gives the cache's arrays and the state row count:
    # one row per KV page, as the engine sizes its state arena today
    build = next(json.loads(ln) for ln in err.splitlines()
                 if ln.startswith('{"phase": "build"'))
    pages = HYBRID["deployment"]["num_pages"]
    assert build["state_slots"] == pages
    held = {a["name"]: a for a in build["arenas"]}
    assert set(held) == {"k_arena", "v_arena", "state.conv", "state.ssm"}
    assert held["state.ssm"]["shape"][2] == pages
    assert held["state.ssm"]["dtype"] == "float32"
    assert build["arena_bytes"] == sum(a["bytes"] for a in held.values())
    arch = R.architecture(c["cfg"])
    assert arch.__file__ == str(tmp_path / "bench/reference/tiny_hybrid.py")
    with pytest.raises(R.Refused, match="no control"):
        arch.logits_at(c["cfg"], 1, [], [], quant=True)
    assert _hashes(tmp_path / "bench").items() >= before.items()
