"""The hybrid by layer pattern (granite-4.0-h) against its plain
reference, ``repro.models.hybrid_ref``, on the reference's seeded random
weights (``init_params``: heads of long and short memory) at a small
size with every multiplier of the published model kept: the full
forward; prefill and decoding through the session runner's decode lanes,
with fewer lanes than sessions so that carries spill to host memory and
come back; and sessions of different lengths stepped in one generate.

Tolerances: the program here runs in float32 (the reduced config) and
the reference in float32 at HIGHEST; they differ only in the order of
float32 sums (the chunked SSD scan against the reference's one token at
a time, blocked attention against a plain softmax). Measured gaps are
about 2e-7 on logits of spread 0.04; 2e-5 leaves a hundredfold margin
and is still a thousandth of the logits' spread.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import hybrid_config, reduced
from repro.models import hybrid_ref as ref
from repro.models import transformer as tfm
from repro.serving.forecaster import LogitProbe, ZooForecaster
from repro.serving.sessions import RecurrentSessionRunner

ATOL = 2e-5


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        reduced(get_config("granite-4.0-h-micro")), n_layers=4,
        layer_types=("mamba", "mamba", "attention", "mamba"))
    return cfg, ref.init_params(hybrid_config(cfg), jax.random.PRNGKey(0),
                                jnp.float32)


def test_reduced_config_keeps_the_published_mechanisms():
    full, small = get_config("granite-4.0-h-micro"), reduced(
        get_config("granite-4.0-h-micro"))
    assert small.layer_types == ("mamba", "attention")
    for name in ("embedding_multiplier", "residual_multiplier",
                 "logits_scaling", "attention_multiplier", "positions",
                 "tie_embeddings", "norm_eps", "ssm_dt_limit"):
        assert getattr(small, name) == getattr(full, name), name
    assert 3.1e9 < full.param_count() < 3.3e9


def test_forward_matches_reference(model):
    cfg, params = model
    toks = np.random.default_rng(1).integers(0, cfg.vocab, (2, 40))
    logits, _ = tfm.lm_forward(cfg, params, toks)
    for b in range(2):
        want = ref.forward(hybrid_config(cfg), params, toks[b])
        np.testing.assert_allclose(np.asarray(logits[b, :, :cfg.vocab]),
                                   np.asarray(want), rtol=0, atol=ATOL)


def _sessions(cfg, lengths, steps, seed):
    rng = np.random.default_rng(seed)
    hist = {f"s{i}": rng.integers(0, cfg.vocab, n)
            for i, n in enumerate(lengths)}
    return hist, {c: rng.integers(0, cfg.vocab, steps) for c in hist}


def _spill_sessions_against_reference(cfg, params):
    """Three sessions over two lanes: every step evicts a lane, whose
    cache spills to host and comes back on the session's next step.
    The logits of every history position (prefill) and of every step
    match the reference's full forward over history and steps. Returns
    the runner and the number of generate waves."""
    hist, steps = _sessions(cfg, (10, 17, 5), 6, seed=2)
    got = {c: [] for c in hist}
    waves = 0
    runner = None

    def sink(kind, key, out):
        nonlocal waves
        logits = np.asarray(out[0])
        if kind == "prefill":
            (c,) = [c for c, h in hist.items()
                    if len(h) == len(key) and (h == key).all()]
            got[c].extend(logits)
        else:
            # inside the runner's generate: its lane map is this wave's
            waves += 1
            who = {lane: c for c, lane in runner._lanes.items()}
            for lane in key:
                got[who[lane]].append(logits[lane])

    fc = ZooForecaster(cfg=cfg, params=params, window=64,
                       probe=LogitProbe(ids=np.arange(cfg.vocab), sink=sink))
    runner = RecurrentSessionRunner(fc, num_slots=2)
    for t in range(6):
        answers = runner.step_many([(c, steps[c][t], hist[c] if t == 0
                                     else None) for c in sorted(hist)])
        assert all(np.isfinite(a).all() for a in answers)
    stats = runner.slot_stats()
    assert stats["spills"] > 0 and stats["inserts"] > 3
    for c in hist:
        want = np.asarray(ref.forward(hybrid_config(cfg), params,
                                      np.concatenate([hist[c], steps[c]])))
        assert len(got[c]) == len(want)
        np.testing.assert_allclose(np.stack(got[c]), want, rtol=0, atol=ATOL)
    return runner, waves


def test_sessions_through_spilling_lanes_match_reference(model):
    """Off a TPU the generate slices each Mamba2 layer's SSM state out
    of the stacked lanes and writes it back (XLA)."""
    runner, waves = _spill_sessions_against_reference(*model)
    assert waves > 0
    assert runner.forecaster.ssm_inplace is False
    assert runner.slot_stats()["ssm_inplace_waves"] == 0


def test_sessions_through_spilling_lanes_update_ssm_state_in_place(
        model, monkeypatch):
    """The generate that a TPU compiles, here with its Pallas kernel in
    interpret mode: each Mamba2 layer's SSM state is updated in place in
    the stacked lanes (``ssd_decode_inplace``), and every wave counts."""
    from repro.kernels.ssd import ops as ssd_ops

    monkeypatch.setattr(ssd_ops, "decode_inplace", lambda: True)
    runner, waves = _spill_sessions_against_reference(*model)
    assert runner.forecaster.ssm_inplace is True
    assert waves > 0 and runner.ssm_inplace_waves == waves
    assert runner.slot_stats()["ssm_inplace_waves"] == waves


def test_sessions_of_different_lengths_in_one_generate(model):
    """Two sessions at different positions step together in one
    generate, each as it steps alone."""
    cfg, params = model
    hist, steps = _sessions(cfg, (7, 19), 4, seed=3)
    out = {}

    def sink(kind, key, probe):
        if kind == "generate":
            out.setdefault(tuple(key), []).append(np.asarray(probe[0]))

    fc = ZooForecaster(cfg=cfg, params=params, window=32,
                       probe=LogitProbe(ids=np.arange(cfg.vocab), sink=sink))

    def run(names):
        slots = fc.init_slots(2)
        for lane, c in enumerate(names):
            _, _, carry = fc.prefill(hist[c][None])
            fc.insert(slots, lane, carry)
        for t in range(4):
            x = np.array([steps[c][t] for c in names] + [0] * (2 - len(names)))
            fc.generate(slots, x, lanes=list(range(len(names))))
        return slots

    slots = run(["s0", "s1"])
    assert list(slots.lengths) == [11, 23]
    assert list(np.asarray(slots.carry["len"])) == [11, 23]
    together = np.stack(out.pop((0, 1)))
    run(["s0"])
    alone0 = np.stack(out.pop((0,)))
    run(["s1"])
    alone1 = np.stack(out.pop((0,)))
    np.testing.assert_allclose(together[:, 0], alone0[:, 0], rtol=0, atol=ATOL)
    np.testing.assert_allclose(together[:, 1], alone1[:, 0], rtol=0, atol=ATOL)


def test_a_full_lane_is_refused(model):
    cfg, params = model
    fc = ZooForecaster(cfg=cfg, params=params, window=8)
    slots = fc.init_slots(1)
    _, _, carry = fc.prefill(np.arange(8)[None])
    fc.insert(slots, 0, carry)
    with pytest.raises(ValueError, match="8 positions"):
        fc.generate(slots, np.zeros((1,), np.int32))


def test_runner_counts_lane_state_and_kv_reads(model):
    """``lane_state_bytes``: one lane's carry by kind; ``kv_tokens_read``:
    over the generate waves, each stepped lane's positions after its
    step."""
    cfg, params = model
    fc = ZooForecaster(cfg=cfg, params=params, window=32)
    runner = RecurrentSessionRunner(fc, num_slots=2)
    n_m, n_a = 3, 1
    N, H, P = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    conv_dim = cfg.d_inner + 2 * N
    assert runner.lane_state_bytes == {
        "ssm": n_m * H * P * N * 4,
        "conv": n_m * (cfg.ssm_conv - 1) * conv_dim * 4,
        "kv": 2 * n_a * 32 * cfg.n_kv_heads * cfg.head_dim * 4,
        "len": 4}
    hist, steps = _sessions(cfg, (5, 9), 3, seed=4)
    runner.step_many([(c, steps[c][0], hist[c]) for c in sorted(hist)])
    assert runner.kv_tokens_read == 6 + 10
    runner.step_many([("s1", steps["s1"][1], None)])
    assert runner.kv_tokens_read == 16 + 11
    assert runner.slot_stats()["kv_tokens_read"] == 27


def test_runner_without_lanes_steps_as_the_lanes_do(model):
    """A session that does not keep a lane steps as a resident one does:
    three sessions churning through ONE lane (each step spills the
    lane's session to the host and inserts the next) answer as three
    sessions resident in three lanes."""
    cfg, params = model
    hist, steps = _sessions(cfg, (6, 11, 9), 3, seed=5)
    answers = []
    for num_slots in (3, 1):
        fc = ZooForecaster(cfg=cfg, params=params, window=32)
        runner = RecurrentSessionRunner(fc, num_slots=num_slots)
        assert runner.num_slots == num_slots
        answers.append([runner.step_many([
            (c, steps[c][t], hist[c] if t == 0 else None)
            for c in sorted(hist)]) for t in range(3)])
    assert runner.slot_stats()["spills"] > 0
    np.testing.assert_allclose(np.asarray(answers[0]),
                               np.asarray(answers[1]), rtol=0, atol=1e-5)
