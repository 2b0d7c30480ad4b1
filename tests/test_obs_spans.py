"""Host spans on the profiler's clock (``repro.obs.trace.span``): every
span the program opens is named in ``SPANS``; a predict flush, a step
flush, a lane miss and a training round open their stages in order and
nested as documented; and the spans land in a real profiler trace under
their names."""

import json
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.async_local_sgd import AsyncLocalSGD, LocalSGDConfig
from repro.core.schedules import SampleSchedule, StepSizeSchedule
from repro.models.rnn import RNNConfig, init_rnn
from repro.obs import trace as obs_trace
from repro.optim.optimizers import sgd
from repro.serving import (BatcherConfig, LSTMForecaster, ModelRegistry,
                           RecurrentSessionRunner, ServingEngine)

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
CFG = RNNConfig(input_dim=3, hidden=8, num_layers=1, fc_dims=(4,),
                window=8, evl_head=True)


class Recorder:
    """Stands in for ``span``: records (thread, "enter"/"exit", name),
    refusing any name ``SPANS`` does not hold."""

    def __init__(self):
        self.events = []

    def __call__(self, name):
        assert name in obs_trace.SPANS, name
        rec = self

        class _Span:
            def __enter__(self):
                rec.events.append((threading.get_ident(), "enter", name))
                return self

            def __exit__(self, *exc):
                rec.events.append((threading.get_ident(), "exit", name))
                return False

        return _Span()

    def trees(self, root):
        """Every span called ``root`` as a nested (name, [children])
        tree, in the order they opened."""
        out, stacks = [], {}
        for tid, kind, name in self.events:
            stack = stacks.setdefault(tid, [])
            if kind == "enter":
                node = (name, [])
                if stack:
                    stack[-1][1].append(node)
                stack.append(node)
            else:
                node = stack.pop()
                assert node[0] == name, (node[0], name)
                if name == root:
                    out.append(node)
        return out

    def count(self, name):
        return sum(1 for _t, k, n in self.events if k == "enter" and n == name)


def names(tree):
    return [child[0] for child in tree[1]]


@pytest.fixture()
def spans(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(obs_trace, "span", rec)
    return rec


@pytest.fixture(scope="module")
def forecaster():
    fc = LSTMForecaster(cfg=CFG, params=init_rnn(jax.random.PRNGKey(0),
                                                 CFG))
    rng = np.random.default_rng(0)
    fc.calibrate(rng.standard_normal((64, CFG.window, 3)).astype(np.float32)
                 * 0.02)
    return fc


def test_every_span_in_src_is_named_in_spans():
    opened = set()
    for root, _dirs, files in os.walk(SRC):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    text = fh.read()
                opened |= set(re.findall(r'\bspan\(\s*"(repro\.[^"]+)"',
                                         text))
                # the helper is the program's one way onto the
                # profiler's clock
                if os.path.join(root, f) != obs_trace.__file__:
                    assert "profiler.TraceAnnotation" not in text, f
    assert opened == set(obs_trace.SPANS)


def test_predict_and_step_flush_stages_in_order(forecaster, spans):
    reg = ModelRegistry()
    reg.register("m", forecaster)
    cfg = BatcherConfig(max_batch=4, max_wait_ms=1.0, length_buckets=(8,),
                        decode_slots=8)
    rng = np.random.default_rng(1)
    with ServingEngine(reg, cfg) as eng:
        eng.warmup("m")
        eng.predict("m", rng.standard_normal((8, 3)).astype(np.float32)
                    * 0.02)
        eng.step("m", "client-a", rng.standard_normal(3).astype(
            np.float32) * 0.02)
    (flush,) = spans.trees("repro.engine.flush.predict")
    assert names(flush) == ["repro.engine.gather", "repro.predict",
                            "repro.readback", "repro.engine.reply"]
    (step,) = spans.trees("repro.engine.flush.step")
    assert names(step) == ["repro.session_step_many", "repro.engine.reply"]
    # a new client: one lane miss (a free lane, so no extract), then
    # the fused generate and its readback
    many = step[1][0]
    assert names(many) == ["repro.sessions.lane_miss",
                           "repro.slots_generate", "repro.readback"]
    assert names(many[1][0]) == ["repro.slots_insert"]
    assert spans.count("repro.engine.wait") >= 1
    assert not spans.trees("repro.engine.wait")[0][1]


def test_one_lane_miss_span_per_lane_insert(forecaster, spans):
    runner = RecurrentSessionRunner(forecaster, num_slots=8)
    assert runner.num_slots == 8
    rng = np.random.default_rng(2)
    before = runner.slot_inserts
    # 16 clients over 8 lanes, a sliding window of 8: each step after
    # the first keeps 4 resident and brings 4 in, evicting the LRU lanes,
    # whose carries spill to the cache
    for r in range(3):
        runner.step_many([(f"c{i}", rng.standard_normal(3).astype(
            np.float32), None) for i in range(r * 4, r * 4 + 8)])
    inserts = runner.slot_inserts - before
    assert inserts == 16 and runner.slot_spills == 8
    misses = spans.trees("repro.sessions.lane_miss")
    assert len(misses) == spans.count("repro.sessions.lane_miss") == inserts
    for miss in misses:
        # an insert in every miss; an extract only where a lane spilled
        assert names(miss) in (["repro.slots_insert"],
                               ["repro.slots_extract", "repro.slots_insert"])
    assert sum(names(m) == ["repro.slots_extract", "repro.slots_insert"]
               for m in misses) == runner.slot_spills


@pytest.fixture(scope="module")
def zoo():
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.models.transformer import init_lm
    from repro.serving import ZooForecaster

    cfg = dataclasses.replace(reduced(get_config("granite-4.0-h-micro")),
                              d_model=64, n_heads=2, n_kv_heads=1,
                              head_dim=32, d_ff=128, vocab=256,
                              ssm_state=16, ssm_head_dim=16)
    return ZooForecaster(cfg=cfg, params=init_lm(cfg, jax.random.PRNGKey(0)),
                         window=16)


def test_zoo_session_steps_open_the_slot_spans(zoo, spans):
    """A token session's step flush runs the lanes as the LSTM's does; a
    lane miss primes from the history under ``repro.zoo.prefill``, and
    on one lane the second client's miss spills the first."""
    reg = ModelRegistry()
    reg.register("z", zoo)
    cfg = BatcherConfig(max_batch=4, max_wait_ms=1.0, decode_slots=1)
    with ServingEngine(reg, cfg) as eng:
        eng.warmup("z", lengths=(8,))
        eng.step("z", "a", 3, history=np.arange(5))
        eng.step("z", "b", 4, history=np.arange(6))
        eng.step("z", "b", 5)
        stats = eng.slot_stats()
    assert stats["inserts"] == 2 and stats["spills"] == 1
    assert stats["kv_tokens_read"] == 6 + 7 + 8
    flushes = spans.trees("repro.engine.flush.step")
    assert len(flushes) == 3
    many = [f[1][0] for f in flushes]
    assert [names(m) for m in many] == [
        ["repro.sessions.lane_miss", "repro.slots_generate",
         "repro.readback"]] * 2 + [["repro.slots_generate",
                                    "repro.readback"]]
    assert names(many[0][1][0]) == ["repro.zoo.prefill", "repro.readback",
                                    "repro.slots_insert"]
    assert names(many[1][1][0]) == ["repro.zoo.prefill", "repro.readback",
                                    "repro.slots_extract",
                                    "repro.slots_insert"]
    assert spans.count("repro.zoo.prefill") == stats["inserts"]


def quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)


@pytest.mark.parametrize("tau,exchange,phases", [
    (0, "model", ["repro.round.local", "repro.round.loss"]),
    (1, "model", ["repro.round.local", "repro.round.exchange",
                  "repro.round.loss"]),
    (0, "gradient", ["repro.round.local", "repro.round.loss"]),
])
def test_round_phases_in_order(spans, tau, exchange, phases):
    cfg = LocalSGDConfig(n_workers=2, tau=tau, exchange=exchange,
                         schedule=SampleSchedule(a=4),
                         stepsize=StepSizeSchedule(eta0=0.05, beta=0.0))
    trainer = AsyncLocalSGD(quad_loss, sgd(), cfg)
    stacked, opt = trainer.init({"w": jnp.zeros((4,))})
    rng = np.random.default_rng(3)
    for r in range(1, 4):
        h = trainer.local_steps_for_round(r)
        x = rng.standard_normal((2, h, 8, 4)).astype(np.float32)
        stacked, opt, _ = trainer.run_round(stacked, opt,
                                            (x, x.sum(-1)))
    rounds = spans.trees("repro.round")
    assert len(rounds) == 3 == trainer.rounds_done
    for rnd in rounds:
        assert names(rnd) == phases
        assert all(not child[1] for child in rnd[1])


PROFILED = r'''
import glob, json, sys
import jax, jax.numpy as jnp
from jax.profiler import ProfileData
from repro.obs.trace import span

out = sys.argv[1]
x = jnp.ones((8,))
float(x.sum())
jax.profiler.start_trace(out)
with span("repro.round"):
    with span("repro.round.loss"):
        float((x * 2).sum())
jax.profiler.stop_trace()
(path,) = glob.glob(out + "/**/*.xplane.pb", recursive=True)
pd = ProfileData.from_file(path)
found = []
for plane in pd.planes:
    for line in plane.lines:
        for e in line.events:
            if e.name.startswith("repro."):
                found.append([e.name, e.start_ns, e.duration_ns])
print(json.dumps(found))
'''


def test_spans_appear_in_a_profiler_trace(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run([sys.executable, "-c", PROFILED, str(tmp_path)],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    found = {n: (a, d) for n, a, d in json.loads(
        done.stdout.strip().splitlines()[-1])}
    assert set(found) == {"repro.round", "repro.round.loss"}
    (a0, d0), (a1, d1) = found["repro.round"], found["repro.round.loss"]
    assert a0 <= a1 and a1 + d1 <= a0 + d0
