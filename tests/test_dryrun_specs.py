"""Dry-run input-spec regressions + a miniature end-to-end dry-run cell.

The prefill specs once carried a train-style ``seq+1`` token length; the
odd length degenerated every chunked kernel to length-1 chunks (6300x on
the memory roofline term — EXPERIMENTS.md §Perf cell 2).  Lock the shapes
down, and compile one real cell on a small debug mesh in a subprocess.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_prefill_specs_have_exact_seq_tokens():
    from repro.configs import SHAPES, get_config
    from repro.launch.dryrun import input_specs

    import jax
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape = SHAPES["prefill_32k"]
    # dense LM: exactly seq tokens (even => chunked kernels stay chunked)
    specs = input_specs(get_config("llama3-8b"), shape, mesh)
    assert specs["tokens"].shape == (shape.batch, shape.seq)
    assert specs["tokens"].shape[1] % 1024 == 0
    # vlm: patches + text fill the sequence exactly
    cfg = get_config("llava-next-mistral-7b")
    specs = input_specs(cfg, shape, mesh)
    assert specs["tokens"].shape[1] + cfg.num_patches == shape.seq
    # audio: frames, not tokens
    cfg = get_config("hubert-xlarge")
    specs = input_specs(cfg, shape, mesh)
    assert specs["frames"].shape == (shape.batch, shape.seq, cfg.frontend_dim)
    # train keeps the +1 (label shift)
    specs = input_specs(get_config("llama3-8b"), SHAPES["train_4k"], mesh)
    assert specs["tokens"].shape == (SHAPES["train_4k"].batch,
                                     SHAPES["train_4k"].seq + 1)


@pytest.mark.slow
def test_dryrun_cell_compiles_on_debug_mesh(tmp_path):
    """Full run_cell path (lower+compile+analyze) on a 2x2 debug mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["REPRO_DRYRUN_DEVICES"] = "4"
    env["REPRO_DRYRUN_MESH"] = "2,2"
    out = str(tmp_path / "cell.json")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; from repro.launch.dryrun import run_cell; "
         f"r = run_cell('mamba2-130m', 'train_4k', 'single', {out!r}); "
         "sys.exit(0 if r['status'] == 'ok' else 1)"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.load(open(out))
    assert rec["status"] == "ok"
    assert rec["hlo_stats"]["dot_flops"] > 0
    assert rec["roofline"]["bottleneck"] in (
        "compute_s", "memory_s", "collective_s")
