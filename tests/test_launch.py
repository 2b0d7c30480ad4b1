"""Launch-layer units that run in the default (1-device) process:
sharding rule construction, input specs, roofline math. The actual
512-device lower+compile runs via ``python -m repro.launch.dryrun``
(separate process; see tests/test_dryrun_subprocess.py)."""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES
from repro.launch import specs as S
from repro.launch.roofline import roofline_terms
from repro.launch.shardings import batch_axes, cache_specs, param_specs

MS = {"data": 16, "model": 16}
MS3 = {"pod": 2, "data": 16, "model": 16}


def test_batch_axes():
    assert batch_axes(MS, 256) == "data"
    assert batch_axes(MS3, 256) == ("pod", "data")
    assert batch_axes(MS, 1) is None
    assert batch_axes(MS3, 2) == "pod"


def test_param_specs_cover_tree():
    for arch in ("mixtral-8x7b", "mamba2-370m", "whisper-medium",
                 "zamba2-2.7b", "qwen3-moe-235b-a22b"):
        cfg = ARCHS[arch]
        pshape = S.params_shape(cfg)
        spec = param_specs(cfg, pshape, MS)
        leaves_p = jax.tree_util.tree_leaves(pshape)
        leaves_s = jax.tree_util.tree_leaves(
            spec, is_leaf=lambda x: isinstance(x, P))
        assert len(leaves_p) == len(leaves_s)
        # every spec's rank matches its leaf and divisibility holds
        for leaf, sp in zip(leaves_p, leaves_s):
            assert len(sp) <= leaf.ndim
            for dim, ax in zip(leaf.shape, tuple(sp) + (None,) * 8):
                if ax is not None:
                    size = np.prod([MS[a] for a in
                                    (ax if isinstance(ax, tuple) else (ax,))])
                    assert dim % size == 0, (arch, leaf.shape, sp)


def test_fully_sharded_biggest_model_fits():
    """qwen3-moe 235B x (bf16 + f32 m + f32 v) must divide below
    16 GiB/chip under the 2-D param sharding."""
    cfg = ARCHS["qwen3-moe-235b-a22b"]
    pshape = S.params_shape(cfg)
    spec = param_specs(cfg, pshape, MS)
    per_chip = 0
    for leaf, sp in zip(jax.tree_util.tree_leaves(pshape),
                        jax.tree_util.tree_leaves(
                            spec, is_leaf=lambda x: isinstance(x, P))):
        shards = 1
        for ax in sp:
            if ax:
                shards *= np.prod([MS[a] for a in
                                   (ax if isinstance(ax, tuple) else (ax,))])
        bytes_ = leaf.size * leaf.dtype.itemsize
        per_chip += bytes_ / shards * (1 + 4 + 4) / leaf.dtype.itemsize \
            if leaf.dtype == np.dtype("bfloat16") else bytes_ / shards
    # bf16 params + 2x f32 adam: ~10B/param fully sharded
    assert per_chip < 16 * 2**30


def test_input_specs_shapes():
    cfg = ARCHS["mixtral-8x7b"]
    tr = S.input_specs(cfg, "train_4k")
    assert tr["tokens"].shape == (256, 4096)
    de = S.input_specs(cfg, "decode_32k")
    assert de["token"].shape == (128,)
    assert "k" in de["cache"]
    # mixtral is native SWA: decode cache is a 4096-slot ring
    assert de["cache"]["k"].shape[2] == 4096
    lg = S.input_specs(cfg, "long_500k")
    assert lg["cache"]["k"].shape[2] == 4096


def test_full_cache_has_write_buffer():
    cfg = ARCHS["chameleon-34b"]
    de = S.input_specs(cfg, "decode_32k")
    assert de["cache"]["k"].shape[2] == 32768
    assert de["cache"]["kr"].shape[2] == cfg.decode_buffer
    spec = cache_specs(cfg, de["cache"], MS, 128)
    assert spec["kr"] == P(None, "data", None, None, None)
    # kv=8 not divisible by 16: main cache shards its sequence dim
    assert spec["k"] == P(None, "data", "model", None, None)


def test_ssm_cache_specs():
    cfg = ARCHS["mamba2-370m"]
    de = S.input_specs(cfg, "long_500k")
    assert "k" not in de["cache"]          # attention-free
    spec = cache_specs(cfg, de["cache"], MS, 1)
    assert spec["ssm"] == P(None, None, "model", None, None)


def test_roofline_terms_math():
    cfg = ARCHS["qwen1.5-4b"]
    shape = INPUT_SHAPES["train_4k"]
    r = roofline_terms(flops_per_chip=1.97e14, bytes_per_chip=819e9,
                       collective_bytes_per_chip=50e9, chips=256,
                       cfg=cfg, shape=shape)
    assert abs(r["compute_s"] - 1.0) < 1e-6
    assert abs(r["memory_s"] - 1.0) < 1e-6
    assert abs(r["collective_s"] - 1.0) < 1e-6
    assert r["dominant"] in ("compute", "memory", "collective")
    assert r["model_flops"] == 6 * cfg.active_param_count() * 256 * 4096


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []          # JAX reads the variable itself


def test_compile_cache_fixed_checkout_path(monkeypatch):
    import os

    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.compile_cache_dir() == want     # never moves
    assert updates == [("jax_compilation_cache_dir", want)]
