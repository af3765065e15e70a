"""End-to-end runs of a toy cell on the CPU: the harness, the engine's
timed path and the reference, with and without a planted fault."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run as R
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def _run(capsys, cell, seed=7, control=False, trace=0, seconds=3.0):
    args = R.parser().parse_args(
        ["--workload", "tiny", "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)] + ["--control"] * control)
    assert R.run(args, cell, require_tpu=False) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def test_chat_cell_is_correct_and_the_control_is_not(capsys):
    seed = 2 ** 33 + 1
    res, err = _run(capsys, tiny.cell(), seed=seed)
    assert res["correct"], res
    assert set(res["metrics"]) >= {"ttft_p90_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "compared"
    assert err.strip().splitlines()[-1].startswith("compared ")
    build = next(json.loads(ln) for ln in err.splitlines()
                 if ln.startswith('{"phase": "build"'))
    assert build["state_slots"] is None
    assert [a["name"] for a in build["arenas"]] == ["k_arena", "v_arena"]
    assert build["arena_bytes"] == sum(a["bytes"] for a in build["arenas"])
    # the float8 control, scored in the program's place, must fail
    ctl, _ = _run(capsys, tiny.cell(), seed=seed, control=True)
    assert ctl["correct"] is False, ctl
    assert ctl["compared"]["max_logit_gap"]["value"] > \
        tiny.CFG["check"]["max_logit_gap"]


def test_an_altered_token_fails(capsys, monkeypatch):
    from repro.serving import engine as E
    inner = E._select_tokens

    def altered(logits, *a, **kw):
        tok = inner(logits, *a, **kw)
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(E, "_select_tokens", altered)
    res, _ = _run(capsys, tiny.cell())
    assert not res["correct"]
    assert res["compared"]["max_logit_gap"]["value"] > \
        tiny.CFG["check"]["max_logit_gap"]


def test_a_compile_inside_the_window_refuses(capsys):
    # warm-up planned for one row; the traffic reaches more, so a decode
    # program compiles inside the window and the run must not report
    args = R.parser().parse_args(["--workload", "tiny", "--seed", "4",
                                  "--seconds", "3"])
    with pytest.raises(R.Refused, match="compiled inside the window"):
        R.run(args, tiny.cell(max_batch=1), require_tpu=False)
    assert capsys.readouterr().out == ""


def test_offline_cell_runs(capsys):
    res, _ = _run(capsys, tiny.cell(tiny.OFFLINE))
    assert res["correct"], res
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0


MESH = r"""
import argparse, json, sys
sys.path[:0] = [{src!r}, {root!r}]
import jax
from bench import run as R
from bench.tests import tiny
if {drop!r}:
    jax.lax.psum = lambda x, axis_name, **kw: x   # the exchange left out
cell = tiny.cell(tiny.OFFLINE, mesh_model=2)
cell["cell"]["chips"] = 2
args = R.parser().parse_args(["--workload", "tiny", "--seed", "3",
                              "--seconds", "2"])
R.run(args, cell, require_tpu=False)
"""


@pytest.mark.parametrize("drop", [False, True])
def test_mesh_exchange_left_out_fails(drop):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    code = MESH.format(src=str(ROOT / "src"), root=str(ROOT), drop=drop)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 2
    assert res["correct"] is (not drop)


def test_no_chip_means_no_result(capsys):
    args = R.parser().parse_args(["--workload", "granite-3-8b.chat-prefix",
                                  "--seed", "1", "--seconds", "1"])
    with pytest.raises(R.Refused):
        R.run(args, R.load_cell("granite-3-8b.chat-prefix"))
    assert capsys.readouterr().out == ""
