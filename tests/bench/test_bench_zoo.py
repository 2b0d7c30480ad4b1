"""The token-session cell (``bench/core/zoo_sessions.py``) driven on the
CPU past the harness's look for a chip, at a size a test run can hold:
sound, it comes out correct; with each of ``zoo_sessions.PLANTS``,
``correct`` comes out false. The conv state dropped on a session's
insert into a lane (``conv_dropped``) moves the logits past
``logit_gap``. The control, the SSM state kept in bfloat16 below the
float32 the configuration states, moves no logit gap the bfloat16
activations do not (at this size and on the chip); it halves the lanes'
SSM bytes, which ``lane_state_off`` reads, and its rounding a step
gathers in the first layer's heads of long memory, which
``ssm_state_gap`` reads: 0.010-0.012 against the sound runs' 0.0038-
0.0046 at this size (seeds 5-7), under the cell's limit of 0.008."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.core import manifest, zoo_sessions  # noqa: E402

CELL = "granite-h-micro-sessions"

SMALL = {"hidden_size": 64, "num_attention_heads": 2,
         "num_key_value_heads": 1, "shared_intermediate_size": 128,
         "intermediate_size": 128, "vocab_size": 512, "mamba_n_heads": 8,
         "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 16,
         "num_hidden_layers": 3,
         "layer_types": ["mamba", "attention", "mamba"]}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the hybrid's session cell at small widths (every
    multiplier and mechanism of the published config kept), whose limits
    are the real configuration's."""
    r = tmp_path_factory.mktemp("checkout")
    os.symlink(os.path.join(ROOT, "src"), r / "src")
    (r / "bench" / "configs").mkdir(parents=True)
    (r / "bench" / "traffic").mkdir()
    os.symlink(os.path.join(ROOT, "bench", "metrics"), r / "bench" / "metrics")
    man = manifest.load(ROOT)
    cfg = manifest.config(man, "granite-4.0-h-micro-serve", ROOT)
    cfg.update(SMALL)
    cfg["assumed"].update(max_len=128, lanes=4)
    cfg["probe_ids"] = 64
    mix = manifest.traffic("hybrid-sessions", ROOT)
    mix["histories"] = [20, 32, 44]
    mix["sources"][0].update(rate_per_s=240, keys=4)
    files = {
        "bench/configs/hybrid.json": cfg,
        "bench/traffic/sessions.json": mix,
        "BENCHMARK.json": {
            "configs": [{"name": "hybrid",
                         "file": "bench/configs/hybrid.json"}],
            "workloads": [{"name": CELL, "config": "hybrid",
                           "traffic": "sessions", "chips": 1}],
            "end_to_end": [m for m in man["end_to_end"]
                           if m["name"] in ("setup_s", "steps_per_s")],
            "per_layer": []}}
    for name, body in files.items():
        with open(r / name, "w") as f:
            json.dump(body, f)
    yield str(r)
    import jax

    jax.config.update("jax_default_matmul_precision", None)


def run(root, seed=5):
    from bench import run as bench_run

    args = types.SimpleNamespace(workload=CELL, seed=seed, seconds=1.0,
                                 trace=0)
    return bench_run.run_cell(args, root=root, require_chip=False,
                              cache=False)[1]


def test_sound_run_is_correct(root):
    line = run(root)
    assert line["correct"], line["checks"]
    assert line["checks"]["lane_state_off"]["value"] == 0
    assert line["attempted"] == 240 and line["failed"] == 0
    assert line["metrics"]["steps_per_s"]["value"] > 0


def caught(root, plant, *checks):
    with zoo_sessions.PLANTS[plant]():
        line = run(root)
    assert not line["correct"], line["checks"]
    for name in checks:
        c = line["checks"][name]
        assert c["value"] > c["limit"], (name, c)


def test_conv_state_dropped_is_caught(root):
    caught(root, "conv_dropped", "logit_gap")


def test_control_is_caught(root):
    caught(root, "control", "lane_state_off", "ssm_state_gap")
