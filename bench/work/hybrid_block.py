"""Operations and least bytes of one decode step of the hybrid by layer
pattern (granite-4.0-h), from the shapes of its published config (``c``:
the configuration file's keys). One step is one token of one session.
The count does not depend on what implements the model.

Per layer kind (d hidden, di = H * P the Mamba2 width, N state, K conv,
C = di + 2 N the conv width, Hq / Hkv heads of size hd, f the MLP width,
V the vocabulary):

- Mamba2 mixer: in_proj 2 d (2 di + 2 N + H); conv 2 C K plus bias and
  silu 2 C; the state update exp(dt A) S + dt x B^T, 3 H P N; the output
  C S, 2 H P N; skip, gate and norm about 8 di; out_proj 2 di d.
- attention mixer: projections 2 d hd (2 Hq + 2 Hkv); per position
  attended, scores and values 4 Hq hd.
- MLP (after every mixer): 2 d f twice and 2 f d, with silu and product
  3 f.
- head: 2 d V, with max and log-sum-exp 3 V.
- each RMSNorm about 4 d.

Least bytes: every weight once per generate wave (the lanes share them;
the tied embedding counted once, as the head), at its dtype's size; per
stepped session its SSM state (float32) and conv state read and written,
and its new token's keys and values written; per position attended, the
keys and values read.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _dims(c: dict) -> dict:
    d = c["hidden_size"]
    H, P = c["mamba_n_heads"], c["mamba_d_head"]
    N = c["mamba_n_groups"] * c["mamba_d_state"]
    di = H * P
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return {"d": d, "H": H, "P": P, "N": N, "di": di, "K": c["mamba_d_conv"],
            "C": di + 2 * N, "hq": hq, "hkv": hkv, "hd": d // hq,
            "f": c["shared_intermediate_size"], "V": c["vocab_size"],
            "n_mamba": c["layer_types"].count("mamba"),
            "n_attn": c["layer_types"].count("attention"),
            "L": len(c["layer_types"])}


def mamba_flops(c: dict) -> float:
    k = _dims(c)
    d, di, N, H, P, C = k["d"], k["di"], k["N"], k["H"], k["P"], k["C"]
    return (2.0 * d * (2 * di + 2 * N + H) + 2.0 * C * k["K"] + 2.0 * C
            + 5.0 * H * P * N + 8.0 * di + 2.0 * di * d + 4.0 * d)


def attention_flops(c: dict, positions: float = 0.0) -> float:
    """One attention layer's step; ``positions`` attended (the new
    token's included)."""
    k = _dims(c)
    proj = 2.0 * k["d"] * k["hd"] * (2 * k["hq"] + 2 * k["hkv"])
    return proj + 4.0 * k["hq"] * k["hd"] * positions + 4.0 * k["d"]


def mlp_flops(c: dict) -> float:
    k = _dims(c)
    return 6.0 * k["d"] * k["f"] + 3.0 * k["f"] + 4.0 * k["d"]


def head_flops(c: dict) -> float:
    k = _dims(c)
    return 2.0 * k["d"] * k["V"] + 3.0 * k["V"] + 4.0 * k["d"]


def step_flops(c: dict, steps: float, kv_positions: float) -> float:
    """Operations of ``steps`` one-token steps whose attention layers
    read ``kv_positions`` positions in all (per layer)."""
    k = _dims(c)
    per = (k["n_mamba"] * mamba_flops(c) + k["n_attn"] * attention_flops(c)
           + k["L"] * mlp_flops(c) + head_flops(c))
    per_position = attention_flops(c, 1.0) - attention_flops(c)
    return steps * per + k["n_attn"] * per_position * kv_positions


def weight_bytes(c: dict, dtype: str = "bfloat16") -> float:
    k = _dims(c)
    d, di, N, H, C = k["d"], k["di"], k["N"], k["H"], k["C"]
    mamba = (d * (2 * di + 2 * N + H) + C * (k["K"] + 1) + 3 * H + di
             + di * d + d)
    attn = d * k["hd"] * (2 * k["hq"] + 2 * k["hkv"]) + d
    mlp = 3 * d * k["f"] + d
    params = (k["n_mamba"] * mamba + k["n_attn"] * attn + k["L"] * mlp
              + k["V"] * d + d)
    return DTYPE_BYTES[dtype] * float(params)


def state_bytes(c: dict, dtypes: dict) -> float:
    """Read and written per stepped session: SSM and conv state, and the
    new token's keys and values."""
    k = _dims(c)
    ssm = DTYPE_BYTES[dtypes["ssm_state"]] * k["H"] * k["P"] * k["N"]
    conv = DTYPE_BYTES[dtypes["conv_state"]] * (k["K"] - 1) * k["C"]
    return k["n_mamba"] * 2.0 * (ssm + conv) + kv_position_bytes(c, dtypes)


def kv_position_bytes(c: dict, dtypes: dict) -> float:
    """Keys and values of one position over every attention layer."""
    k = _dims(c)
    return (k["n_attn"] * 2.0 * k["hkv"] * k["hd"]
            * DTYPE_BYTES[dtypes["kv"]])


def wave_bytes(c: dict, dtypes: dict, waves: float, steps: float,
               kv_positions: float) -> float:
    """Least bytes of ``waves`` generate waves that stepped ``steps``
    sessions in all, whose attention read ``kv_positions`` positions."""
    return (waves * weight_bytes(c, dtypes["weights"])
            + steps * state_bytes(c, dtypes)
            + kv_positions * kv_position_bytes(c, dtypes))
