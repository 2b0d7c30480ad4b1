"""Plain reference of the Granite 4.0-H hybrid language model
(``model_type: granitemoehybrid``; https://huggingface.co/ibm-granite/
granite-4.0-h-micro): the forward pass of one token sequence in
straightforward ``jax.numpy`` and float32, every matrix product at
``HIGHEST`` precision, one function per module of the published model.

``c`` is the published ``config.json`` as a dict (its key names:
``hidden_size``, ``layer_types``, ``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_d_conv``, ``mamba_n_groups``,
``num_attention_heads``, ``num_key_value_heads``,
``attention_multiplier``, ``embedding_multiplier``,
``residual_multiplier``, ``logits_scaling``, ``rms_norm_eps``,
``tie_word_embeddings``), with ``time_step_limit``, granitemoehybrid's
default clip of dt, ``(0, inf)``.

The equations (HF ``GraniteMoeHybrid*``), for a layer of either kind::

    x <- x + r * mixer(rmsnorm(x))        r = residual_multiplier
    x <- x + r * mlp(rmsnorm(x))          mlp(h) = (silu(h W1) * h W3) W2

the embedding is scaled by ``embedding_multiplier`` and the logits are
``rmsnorm(x) E^T / logits_scaling`` (tied head). Attention has no
positional encoding and scales its scores by ``attention_multiplier``.
The Mamba2 mixer, per token t and head h (one group)::

    z, xBC, dt = split(h_t Win)
    xBC = silu(causal depthwise conv(xBC) + conv bias)
    x, B, C = split(xBC)
    dt = clip(softplus(dt + dt_bias), *time_step_limit)
    state_h <- exp(dt_h * A_h) state_h + dt_h x_h B^T     A = -exp(A_log)
    y_h = state_h C + D_h x_h
    out = rmsnorm(y * silu(z)) Wout                       (gated RMSNorm)

Departures from the published model, all of them deliberate:

- float32 throughout, where the checkpoint is bfloat16;
- the weights are read from this repository's parameter layout, not
  the checkpoint's names: ``embed`` [V, d], ``final_norm.w``; per Mamba2
  layer (stacked in ``mamba``) ``norm.w`` and ``ssm.{in_proj [d,
  2 di + 2 N + H], conv_w [di + 2 N, K], conv_b, dt_bias, A_log, D,
  norm_w, out_proj [di, d]}``; per attention layer (stacked in ``attention``)
  ``norm.w`` and ``attn.{wq, wk, wv, wo}`` as ``x @ w``; per layer
  (stacked in ``mlp``) ``norm.w`` and ``mlp.{w1 (gate), w3 (up), w2}``;
  ``init_params`` draws random weights in that layout from a seed (no
  checkpoint is read);
- the SSM runs one token at a time (the published model's chunked scan
  computes the same recurrence);
- one sequence, no cache and no batching.

This file has a copy under ``bench/configs/``, which the benchmark
holds the program to; it imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mamba_mixer(c, p, h, n=None):
    """The Mamba2 mixer on normed inputs ``h`` [T, d]: (output [T, d],
    the SSM state [H, P, N] after the first ``n`` positions, default
    all T; later positions, padding, leave it as it is)."""
    p = _f32(p)
    di = c["mamba_n_heads"] * c["mamba_d_head"]
    N = c["mamba_n_groups"] * c["mamba_d_state"]
    H, P = c["mamba_n_heads"], c["mamba_d_head"]
    T = h.shape[0]
    zxbcdt = _mm(h, p["in_proj"])
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * N]
    dt = zxbcdt[:, 2 * di + 2 * N:]
    K = p["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = p["conv_b"] + sum(padded[k:k + T] * p["conv_w"][:, k]
                             for k in range(K))
    xbc = silu(conv)
    x = xbc[:, :di].reshape(T, H, P)
    B = xbc[:, di:di + N]
    C = xbc[:, di + N:]
    lo, hi = c["time_step_limit"]
    dt = jnp.clip(jax.nn.softplus(dt + p["dt_bias"]), lo, hi)      # [T, H]
    A = -jnp.exp(p["A_log"])

    n = T if n is None else n

    def step(carry, inp):
        state, kept = carry
        x_t, B_t, C_t, dt_t, t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        y_t = jnp.einsum("hpn,n->hp", state, C_t, precision=HIGHEST)
        kept = jnp.where(t < n, state, kept)
        return (state, kept), y_t + p["D"][:, None] * x_t

    zero = jnp.zeros((H, P, N))
    (_, kept), y = jax.lax.scan(step, (zero, zero),
                                (x, B, C, dt, jnp.arange(T)))
    y = y.reshape(T, di) * silu(z)
    y = rms_norm(y, p["norm_w"], c["rms_norm_eps"])
    return _mm(y, p["out_proj"]), kept


def attention(c, p, h):
    """Causal GQA attention with no positional encoding on normed
    inputs ``h`` [T, d]."""
    p = _f32(p)
    T = h.shape[0]
    Hq, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // Hq
    q = _mm(h, p["wq"]).reshape(T, Hq, hd)
    k = jnp.repeat(_mm(h, p["wk"]).reshape(T, Hkv, hd), Hq // Hkv, axis=1)
    v = jnp.repeat(_mm(h, p["wv"]).reshape(T, Hkv, hd), Hq // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    s = s * c["attention_multiplier"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)
    return _mm(out.reshape(T, Hq * hd), p["wo"])


def mlp(p, h):
    p = _f32(p)
    return _mm(silu(_mm(h, p["w1"])) * _mm(h, p["w3"]), p["w2"])


def layer(c, kind, mixer_p, mlp_p, x, n=None):
    """One decoder layer of either kind on the residual stream x [T, d]:
    (x, a Mamba2 layer's SSM state after the first ``n`` positions, as
    ``mamba_mixer`` gives it; None for attention)."""
    r, eps = c["residual_multiplier"], c["rms_norm_eps"]
    h = rms_norm(x, jnp.asarray(mixer_p["norm"]["w"], jnp.float32), eps)
    state = None
    if kind == "mamba":
        y, state = mamba_mixer(c, mixer_p["ssm"], h, n)
        x = x + r * y
    else:
        x = x + r * attention(c, mixer_p["attn"], h)
    h = rms_norm(x, jnp.asarray(mlp_p["norm"]["w"], jnp.float32), eps)
    return x + r * mlp(mlp_p["mlp"], h), state


def embed(c, params, tokens):
    e = jnp.asarray(params["embed"], jnp.float32)
    return e[tokens] * c["embedding_multiplier"]


def logits(c, params, x):
    """Logits [T, V] of the residual stream x [T, d]."""
    h = rms_norm(x, jnp.asarray(params["final_norm"]["w"], jnp.float32),
                 c["rms_norm_eps"])
    head = (params["embed"].T if c["tie_word_embeddings"]
            else params["lm_head"])
    return _mm(h, jnp.asarray(head, jnp.float32)) / c["logits_scaling"]


def layer_params(params, c, i):
    """(kind, mixer params, mlp params) of layer ``i``."""
    kind = c["layer_types"][i]
    k = c["layer_types"][:i].count(kind)
    stack = params[kind]
    take = lambda t: jax.tree.map(lambda a: a[k], t)  # noqa: E731
    return kind, take(stack), jax.tree.map(lambda a: a[i], params["mlp"])


def forward(c, params, tokens):
    """Logits [T, V] of every position of one sequence ``tokens`` [T]."""
    x = embed(c, params, jnp.asarray(tokens))
    for i in range(len(c["layer_types"])):
        kind, mp, fp = layer_params(params, c, i)
        x, _ = layer(c, kind, mp, fp, x)
    return logits(c, params, x)


def init_params(c, key, dtype=jnp.bfloat16):
    """Random weights from ``key`` in the layout above, as a benchmark
    configuration assumes them: projections N(0, 1/fan_in), the
    embedding N(0, 0.02), norm weights and D 1; mamba_ssm's decay and
    step, A_log = log U[1, 16] and dt_bias = softplus^-1(exp U[log 1e-3,
    log 1e-1]) (heads of long and short memory, as trained checkpoints
    have), both float32; the depthwise conv as torch's Conv1d,
    U(-K^-1/2, K^-1/2). Every other leaf in ``dtype``."""
    d, V, L = c["hidden_size"], c["vocab_size"], len(c["layer_types"])
    H, P = c["mamba_n_heads"], c["mamba_d_head"]
    di, N = H * P, c["mamba_n_groups"] * c["mamba_d_state"]
    K, conv = c["mamba_d_conv"], H * P + 2 * N
    Hq, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd, f = d // Hq, c["shared_intermediate_size"]
    n_m, n_a = c["layer_types"].count("mamba"), c["layer_types"].count(
        "attention")
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std=None):
        std = shape[-2] ** -0.5 if std is None else std
        return (jax.random.normal(next(keys), shape) * std).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    step = jnp.exp(uniform((n_m, H), jnp.log(1e-3), jnp.log(1e-1)))
    bound = K ** -0.5
    params = {"embed": normal((V, d), 0.02), "final_norm": {"w": ones(d)},
              "mlp": {"norm": {"w": ones(L, d)},
                      "mlp": {"w1": normal((L, d, f)),
                              "w3": normal((L, d, f)),
                              "w2": normal((L, f, d))}}}
    if not c["tie_word_embeddings"]:
        params["lm_head"] = normal((d, V))
    if n_m:
        params["mamba"] = {"norm": {"w": ones(n_m, d)}, "ssm": {
            "in_proj": normal((n_m, d, 2 * di + 2 * N + H)),
            "conv_w": uniform((n_m, conv, K), -bound, bound).astype(dtype),
            "conv_b": uniform((n_m, conv), -bound, bound).astype(dtype),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(uniform((n_m, H), 1.0, 16.0)),
            "D": ones(n_m, H), "norm_w": ones(n_m, di),
            "out_proj": normal((n_m, di, d))}}
    if n_a:
        params["attention"] = {"norm": {"w": ones(n_a, d)}, "attn": {
            "wq": normal((n_a, d, Hq * hd)),
            "wk": normal((n_a, d, Hkv * hd)),
            "wv": normal((n_a, d, Hkv * hd)),
            "wo": normal((n_a, Hq * hd, d))}}
    return params
