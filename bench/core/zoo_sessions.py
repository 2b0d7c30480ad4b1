"""A token model's streaming sessions as the benchmark runs them: a
hybrid by layer pattern (granite-4.0-h) at the configuration's widths
behind the serving engine, one session per decode lane, offered an
open-loop schedule of one-token steps.

Set-up (timed as ``setup_s``): the weights, drawn on the device from
the seed by the reference's ``init_params`` in one jitted call; the
engine started; every session primed through the engine with its
history and first token (a lane miss prefills the history, which
compiles one prefill a history length, and the runner's construction
compiles the lane programs); the EVT alert calibrated on the surprisal
of every history position the prefills computed; and the schedule with
every token built. The window then sends each step at its due time
from one generator thread and times it from that due time to its
answer.

What the timed path produced is read through the forecaster's
``LogitProbe``: at every position of every prefill and for every lane
of every generate wave, the logits of a seeded set of vocabulary ids,
with the maximum and the log-sum-exp over the whole vocabulary, as
device arrays kept until the window closes. After it, a seeded sample of
sessions is compared, every position of its history and every one of
its steps, with the plain reference (``bench/configs/granite_hybrid_
ref.py``) computed on the chip in float32 at HIGHEST one layer at a time,
and their lanes, spilled to host, with the configuration's widths and
dtypes and with the reference's SSM state (see ``compare``).

``PLANTS`` are the faults this cell's comparison is shown to catch
(``tests/bench/test_bench_zoo.py``): ``control``, the SSM state kept in
bfloat16, below the float32 the configuration states; ``conv_dropped``,
the conv state of a session dropped when it is inserted into a lane.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import time

import numpy as np

from bench.core import serve, stats, traffic
from bench.core.run_context import Run

KEY = "zoo"
SAMPLE = 4                  # sessions compared with the reference
STALL_S = 0.05              # a send this late marks a stall of the host
REF_BLOCK = 512             # positions of reference logits at a time


def ref_config(cfg: dict) -> dict:
    """The published config (the file's top-level keys) with the dt
    limit the configuration assumes, as the reference reads it."""
    lo, hi = cfg["assumed"]["time_step_limit"]
    return dict(cfg, time_step_limit=(float(lo), float(hi)))


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` of the configuration's widths, and
    whether they are the registered arch's own."""
    from repro.configs import get_config
    from repro.configs.base import from_hybrid_config

    base = get_config(cfg["arch"])
    arch = dataclasses.replace(from_hybrid_config(ref_config(cfg), base),
                               dtype=cfg["assumed"]["dtypes"]["weights"])
    return arch, arch == base


class Traffic:
    """The schedule, every session's history and every token, from the
    seed. Token ids are Zipf-distributed over the vocabulary (ranks
    given to ids by a seeded permutation); histories take the mix's
    lengths in equal shares."""

    def __init__(self, mix: dict, cfg: dict, seconds: float, seed: int,
                 scale: float = 1.0):
        (src,) = mix["sources"]
        self.sched = traffic.schedule(mix, seconds, seed, scale)
        self.kinds = ["step"]
        n = len(self.sched)
        n_keys = int(src["keys"])
        V = int(cfg["vocab_size"])
        rng = traffic.rng_for(seed, 2, 0)
        w = traffic.zipf_weights(V, float(mix["token_zipf_s"]))
        ids = rng.permutation(V)

        def tokens(k):
            return ids[rng.choice(V, size=k, p=w / w.sum())].astype(np.int32)

        lengths = np.resize(np.asarray(mix["histories"], np.int64), n_keys)
        rng.shuffle(lengths)
        self.history = {k: tokens(int(lengths[k])) for k in range(n_keys)}
        self.first = {k: tokens(1)[0] for k in range(n_keys)}
        self.payload = list(tokens(n))
        self.client = [f"s{k}" for k in self.sched.key]
        self.steps = {k: [] for k in range(n_keys)}      # request indices
        for j, k in enumerate(self.sched.key):
            self.steps[int(k)].append(j)
        cap = int(cfg["assumed"]["max_len"])
        longest = max(len(self.history[k]) + 1 + len(self.steps[k])
                      for k in range(n_keys))
        if longest > cap:
            raise SystemExit(f"bench: a session would hold {longest} "
                             f"positions, over the lanes' {cap}")


class Recorder:
    """The ``LogitProbe`` sink: what the timed path's programs computed,
    kept as device arrays (no readback while serving). ``sampled`` maps
    the history of each compared session to its key."""

    def __init__(self, sampled: dict):
        self.sampled = sampled
        self.prefills = {}      # session key -> (logits, max, lse)
        self.surprisal = []     # (max, lse) of every prefill
        self.waves = []         # (lanes stepped, (logits, max, lse))

    def __call__(self, kind, key, out):
        if kind == "generate":
            self.waves.append((key, out))
            return
        self.surprisal.append(out[1:])
        k = self.sampled.get(np.asarray(key, np.int32).tobytes())
        if k is not None:
            self.prefills[k] = out


class Keyed:
    """The engine as ``serve.drive`` addresses it, every step sent to
    this cell's model."""

    def __init__(self, engine):
        self.engine = engine

    def submit_step(self, _key, client, x):
        return self.engine.submit_step(KEY, client, x)


def weights(cfg: dict, seed: int):
    """The weights, drawn on the device from the seed by the reference's
    own ``init_params`` in the dtype the configuration states; the
    program is handed them as they are, their layout checked against
    the one it builds itself."""
    import jax
    import jax.numpy as jnp

    from bench.configs import granite_hybrid_ref as ref
    from repro.models.transformer import init_lm

    arch, _ = arch_config(cfg)
    draw = functools.partial(ref.init_params, ref_config(cfg),
                             dtype=jnp.dtype(cfg["assumed"]["dtypes"]
                                             ["weights"]))
    params = jax.jit(draw)(traffic.jax_key(seed))
    layout = lambda t: jax.tree.map(  # noqa: E731
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    want = layout(jax.eval_shape(functools.partial(init_lm, arch),
                                 jax.random.PRNGKey(0)))
    if layout(params) != want:
        raise SystemExit("bench: the reference's weights are not in the "
                         "program's layout")
    return params


def build_program(cfg: dict, seed: int, probe):
    """The registry, forecaster and engine, as a deployment builds them,
    serving the reference's weights."""
    from repro.serving import (BatcherConfig, ModelRegistry, ServingEngine,
                               ZooForecaster)

    arch, published = arch_config(cfg)
    fc = ZooForecaster(cfg=arch, params=weights(cfg, seed),
                       gamma=float(cfg["alert"]["gamma"]),
                       window=int(cfg["assumed"]["max_len"]), probe=probe)
    registry = ModelRegistry()
    registry.register(KEY, fc)
    b = cfg["batcher"]
    bc = BatcherConfig(max_batch=b["max_batch"], max_wait_ms=b["max_wait_ms"],
                       decode_slots=int(cfg["assumed"]["lanes"]))
    return registry, ServingEngine(registry, bc), published


def counters(engine) -> dict:
    t = engine.telemetry
    slots = engine.slot_stats()
    return {"steps": t.step_requests, "step_flushes": t.step_batches,
            "lane_slots": t.step_padded_slots,
            "slot_inserts": slots["inserts"], "slot_spills": slots["spills"],
            "kv_tokens_read": slots["kv_tokens_read"]}


def prime(engine, tr: Traffic) -> None:
    futs = [engine.submit_step(KEY, f"s{k}", tr.first[k],
                               history=tr.history[k])
            for k in sorted(tr.history)]
    for f in futs:
        f.result(timeout=1800.0)


def calibrate(fc, rec: Recorder, quantile: float) -> None:
    """The alert's tail, fitted on the surprisal (log-sum-exp less the
    largest logit) of every history position the prefills computed; a
    traced argument of the programs, so nothing compiles again."""
    from repro.extreme.evt import fit_tail

    s = np.concatenate([np.asarray(lse) - np.asarray(mx)
                        for mx, lse in rec.surprisal])
    fc.tail = fit_tail(s, q=quantile)


def probe_ids(cfg: dict, seed: int) -> np.ndarray:
    V = int(cfg["vocab_size"])
    k = min(int(cfg["probe_ids"]), V)
    return np.sort(traffic.rng_for(seed, 5).choice(V, size=k, replace=False))


def run(spec) -> Run:
    cfg, mix, seed, seconds = spec.config, spec.mix, spec.seed, spec.seconds
    run = Run(spec)
    tr = Traffic(mix, cfg, seconds, seed)
    rng = traffic.rng_for(seed, 4)
    sample = sorted(rng.choice(len(tr.history), size=min(
        SAMPLE, len(tr.history)), replace=False).tolist())
    rec = Recorder({tr.history[k].tobytes(): k for k in sample})
    from repro.serving.forecaster import LogitProbe

    ids = probe_ids(cfg, seed)
    registry, engine, published = build_program(
        cfg, seed, LogitProbe(ids=ids, sink=rec))
    fc = registry.get(KEY)
    engine.start()
    try:
        prime(engine, tr)
        calibrate(fc, rec, float(cfg["alert"]["quantile"]))
        lanes = engine.session_lanes(KEY)
        before = counters(engine)
        run.setup_done()
        with run.window():
            outs, due, done, lag = serve.drive(Keyed(engine), tr, seconds)
        after = counters(engine)
        run.read_memory()
        lanes_after = engine.session_lanes(KEY)
        lane_bytes = engine.slot_stats()["lane_state_bytes"]
        # the compared sessions' carries as the program spills them to
        # host memory, out of their lanes (after the window)
        spilled = {cid: carry for cid, carry, _n, _v in
                   engine.snapshot_sessions([f"s{k}" for k in sample])}
    finally:
        engine.stop()
    params = fc.params
    # the engine and its runner hold each other, so only the collector
    # frees the lanes' 7 GB: unfreeze what set-up froze, before the
    # reference needs the room
    del registry, engine, fc
    gc.unfreeze()
    gc.collect()
    lat = done - due
    lat[[o is None for o in outs]] = np.inf
    lat[np.isnan(lat)] = np.inf
    close = due[0] - tr.sched.t[0] + seconds
    run.requests = {"step": lat}
    run.completed = {"step": int(np.sum(np.isfinite(lat) & (done <= close)))}
    run.attempted = len(outs)
    run.failed = sum(o is None for o in outs)
    run.lag_s = lag
    run.stalls = stats.stalls(tr.sched.t, lag, STALL_S)
    run.counters = {k: after[k] - before[k] for k in before}
    lanes_n = int(cfg["assumed"]["lanes"])
    run.work = {"step": {"waves": run.counters["lane_slots"] / lanes_n,
                         "steps": run.counters["steps"],
                         "kv_tokens_read": run.counters["kv_tokens_read"]}}
    run.checks, info = compare(cfg, params, tr, rec, lanes, outs, sample,
                               ids, spilled)
    if lanes_after != lanes:
        run.checks["lanes_moved"] = {"value": 1, "limit": 0}
    run.info = dict(info, lanes=lanes_n, published_widths=published,
                    lane_state_bytes=lane_bytes,
                    waves=run.work["step"]["waves"])
    return run


def _program_rows(rec, waves, lanes, k, n_steps):
    """(logits [P, K], max [P], lse [P]) of session ``k``'s positions as
    the timed path computed them: its history's (prefill), then one per
    generate wave that stepped its lane; None where a count is off."""
    pre = rec.prefills.get(k)
    lane = lanes.get(f"s{k}")
    if pre is None or lane is None:
        return None
    rows = [[np.asarray(a)] for a in pre]
    for stepped, out in waves:
        if lane in stepped:
            for r, a in zip(rows, out):
                r.append(a[lane][None])
    if len(rows[1]) != 1 + n_steps:
        return None
    return [np.concatenate(r) for r in rows]


class Reference:
    """The reference's (logits at ``ids``, max, lse) at every position of
    a sequence, and its first Mamba2 layer's SSM state after the last,
    on the chip in float32 at HIGHEST, one layer at a time (one layer's
    weights in float32 at once), the sequence padded to a multiple of
    ``REF_BLOCK`` so that few programs serve every session."""

    def __init__(self, cfg: dict, ids):
        import jax
        import jax.numpy as jnp

        from bench.configs import granite_hybrid_ref as ref

        self.ref = ref
        self.c = c = ref_config(cfg)
        self.layer = {kind: jax.jit(functools.partial(ref.layer, c, kind))
                      for kind in ("mamba", "attention")}
        self.embed = jax.jit(functools.partial(ref.embed, c))
        ids = jnp.asarray(ids)

        @jax.jit
        def head(params, xb):
            lg = ref.logits(c, params, xb)[:, :c["vocab_size"]]
            return (jnp.take(lg, ids, axis=1), lg.max(-1),
                    jax.nn.logsumexp(lg, axis=-1))

        self.head = head

    def rows(self, params, tokens):
        T = len(tokens)
        padded = np.zeros(-(-T // REF_BLOCK) * REF_BLOCK, np.int32)
        padded[:T] = tokens
        x = self.embed(params, padded)
        first = None
        for i in range(len(self.c["layer_types"])):
            kind, mp, fp = self.ref.layer_params(params, self.c, i)
            x, state = self.layer[kind](mp, fp, x, np.int32(T))
            if first is None and state is not None:
                first = np.asarray(state, np.float64)
        outs = [self.head(params, x[lo:lo + REF_BLOCK])
                for lo in range(0, len(padded), REF_BLOCK)]
        return [np.concatenate([np.asarray(o[j]) for o in outs])[:T]
                for j in range(3)], first


def lane_bytes(cfg: dict) -> dict:
    """One lane's state in bytes, by kind, as the configuration's widths
    and dtypes give it: the SSM state and the last ``mamba_d_conv`` - 1
    conv inputs of each Mamba2 layer, and the keys and values of
    ``max_len`` positions of each attention layer."""
    import jax.numpy as jnp

    kinds = cfg["layer_types"]
    size = {k: jnp.dtype(cfg["assumed"]["dtypes"][k]).itemsize
            for k in ("ssm_state", "conv_state", "kv")}
    n_m, n_a = kinds.count("mamba"), kinds.count("attention")
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return {"ssm": n_m * H * P * N * size["ssm_state"],
            "conv": n_m * (cfg["mamba_d_conv"] - 1) * (H * P + 2 * N)
            * size["conv_state"],
            "kv": 2 * n_a * int(cfg["assumed"]["max_len"])
            * cfg["num_key_value_heads"] * hd * size["kv"]}


def lane_state_off(cfg: dict, carries) -> float:
    """The largest relative gap, over the spilled carries and the kinds
    of state, between a carry's bytes and ``lane_bytes``: 0 where every
    lane holds its state at the configuration's widths and dtypes; a
    carry that does not come back reads +inf."""
    want = lane_bytes(cfg)
    worst = 0.0
    for carry in carries:
        if carry is None:
            return float("inf")
        got = {"ssm": carry["ssm"].nbytes, "conv": carry["conv"].nbytes,
               "kv": carry["k"].nbytes + carry["v"].nbytes}
        worst = max([worst] + [abs(got[k] / want[k] - 1) for k in want])
    return float(worst)


def long_memory_heads(params, steps: int) -> np.ndarray:
    """The first Mamba2 layer's heads whose memory outlasts ``steps``
    steps, or (where none does) the one with the longest: a head's decay
    a step at a zero input is softplus(dt_bias) exp(A_log), and it
    keeps about the inverse of that many steps; from the weights."""
    ssm = params["mamba"]["ssm"]
    rate = np.log1p(np.exp(np.asarray(ssm["dt_bias"][0], np.float64))) \
        * np.exp(np.asarray(ssm["A_log"][0], np.float64))
    heads = np.flatnonzero(rate * steps < 1)
    return heads if len(heads) else np.argsort(rate)[:1]


def state_gap(got, want, heads) -> float:
    """The largest, over ``heads``, of a head's SSM state gap [P, N] in
    units of the reference state's norm."""
    got = np.asarray(got, np.float64)
    return float(max(np.linalg.norm(got[h] - want[h])
                     / np.linalg.norm(want[h]) for h in heads))


def compare(cfg: dict, params, tr: Traffic, rec: Recorder, lanes: dict,
            outs, sample, ids, spilled: dict):
    """Every position the timed path computed for each sampled session:
    its history's, by prefill, and each of its steps', by the generate
    waves (the priming step and every step of the window), against the
    reference's full forward over history and steps; then the session's
    lane state, spilled to host after the window, against the
    configuration and the reference. Returns (checks, info):

    - ``logit_gap``: the widest gap over those positions of a probed
      logit, the largest logit or the log-sum-exp, in units of the
      spread (standard deviation) of the reference's probed logits of
      the session, so that it reads alike at any width (the logits'
      spread grows as the root of the hidden size).
    - ``lane_state_off``: ``lane_state_off`` of the spilled carries.
    - ``ssm_state_gap``: the first Mamba2 layer's SSM state after the
      session's last step, against the reference's, by the worst of the
      ``long_memory_heads`` over the session's steps. The first layer's
      input is the embedding, the same on both sides, so its state
      carries one layer's rounding of the activations, which a head of
      long memory averages over many steps; a state stored in a lower
      precision gathers a rounding every step it is kept. Deeper layers
      carry every earlier layer's rounding of the activations, as
      large, and a head of short memory forgets the stored rounding.

    A session whose positions or state do not all come back reads +inf."""
    gaps, states = [], []
    compared = 0
    reference = Reference(cfg, ids)
    waves = [(stepped, [np.asarray(a) for a in out])
             for stepped, out in rec.waves]
    for k in sample:
        steps = [tr.first[k]] + [tr.payload[j] for j in tr.steps[k]]
        got = None if any(outs[j] is None for j in tr.steps[k]) \
            else _program_rows(rec, waves, lanes, k, len(steps))
        carry = spilled.get(f"s{k}")
        if got is None or carry is None:
            gaps.append(np.inf)
            states.append(np.inf)
            continue
        tokens = np.concatenate([tr.history[k], np.asarray(steps, np.int32)])
        want, state = reference.rows(params, tokens)
        spread = float(np.std(want[0]))
        gaps.append(max(float(np.max(np.abs(g - w)))
                        for g, w in zip(got, want)) / spread)
        states.append(state_gap(carry["ssm"][0, 0], state,
                                long_memory_heads(params, len(steps))))
        compared += len(tokens)
    lim = cfg["limits"]
    checks = {
        "unanswered": {"value": sum(o is None for o in outs), "limit": 0},
        "logit_gap": {"value": float(max(gaps)), "limit": lim["logit_gap"]},
        "lane_state_off": {
            "value": lane_state_off(cfg, [spilled.get(f"s{k}")
                                          for k in sample]),
            "limit": lim["lane_state_off"]},
        "ssm_state_gap": {"value": float(max(states)),
                          "limit": lim["ssm_state_gap"]},
    }
    return checks, {"positions_compared": compared,
                    "gap_by_session": [float(g) for g in gaps],
                    "state_gap_by_session": [float(g) for g in states]}


# -- plants -----------------------------------------------------------------

@contextlib.contextmanager
def _patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def ssm_state_bf16():
    """The control: every SSM state the program allocates (the lanes',
    a prefill's, a fresh session's) in bfloat16, one step below the
    float32 the configuration states; each step then stores its state
    rounded to it."""
    import jax.numpy as jnp

    import repro.models.transformer as tfm

    def make(orig):
        def _ssm_cache(cfg, batch, n_layers, dt):
            cache = orig(cfg, batch, n_layers, dt)
            cache["ssm"] = cache["ssm"].astype(jnp.bfloat16)
            return cache
        return _ssm_cache

    return _patched(tfm, "_ssm_cache", make)


def conv_dropped():
    """A session's conv state (the last inputs of each Mamba2 layer's
    causal conv) dropped when it is inserted into a lane."""
    from repro.serving.forecaster import ZooForecaster

    def make(orig):
        def insert(self, slots, lane, carry, donate=None):
            carry = dict(carry, conv=np.zeros(np.shape(carry["conv"]),
                                              carry["conv"].dtype))
            return orig(self, slots, lane, carry, donate)
        return insert

    return _patched(ZooForecaster, "insert", make)


PLANTS = {"control": ssm_state_bf16, "conv_dropped": conv_dropped}


def sweep(spec, scales, seconds: float):
    """The knee: the cell set up once, then its mix offered at each rate
    multiplier for ``seconds``; yields one dict per rate."""
    from bench.core.run_context import freeze_heap

    cfg, mix, seed = spec.config, spec.mix, spec.seed
    top = max(scales)
    tr0 = Traffic(mix, cfg, seconds * len(scales), seed, top)
    registry, engine, _ = build_program(cfg, seed, None)
    engine.start()
    try:
        prime(engine, tr0)
        for scale in scales:
            tr = Traffic(mix, cfg, seconds, seed + int(scale * 1000), scale)
            freeze_heap()
            t0 = time.perf_counter()
            outs, due, done, lag = serve.drive(Keyed(engine), tr, seconds)
            lat = done - due
            lat[[o is None for o in outs]] = np.inf
            answered = np.isfinite(lat)
            third = max(1, len(lat) // 3)
            last = float(np.nanmax(done[answered])) - t0 \
                if answered.any() else float("nan")
            yield {"scale": scale, "offered_per_s": len(lat) / seconds,
                   "answered_per_s": int(answered.sum()) / max(last, 1e-9),
                   "in_window_per_s": int(np.sum(
                       answered & (done <= t0 + seconds))) / seconds,
                   "failed": int((~answered).sum()),
                   "p50_ms": stats.percentile(lat, 50) * 1e3,
                   "p99_ms": stats.percentile(lat, 99) * 1e3,
                   "backlog_growth": stats.median(lat[-third:])
                   / max(stats.median(lat[:third]), 1e-9)}
    finally:
        engine.stop()
