"""Substrate tests: optimizer, data pipeline, checkpointing, fault
tolerance, gradient compression, sharding-rule inference."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import Prefetcher, TokenDataset
from repro.optim.optimizer import (AdamWConfig, adamw_init, adamw_update,
                                   clip_by_global_norm, cosine_schedule,
                                   global_norm)
from repro.parallel import compression
from repro.train import checkpoint as ckpt

from conftest import assert_allclose


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=200,
                      weight_decay=0.0, clip_norm=1e9)
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    opt = adamw_init(params, cfg)
    loss = lambda p: jnp.sum((p["w"] - target) ** 2)
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(g, opt, params, cfg)
    assert float(loss(params)) < 1e-3


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}
    assert abs(float(global_norm(g)) - 10.0) < 1e-5
    clipped, gn = clip_by_global_norm(g, 5.0)
    assert abs(float(global_norm(clipped)) - 5.0) < 1e-4
    assert abs(float(gn) - 10.0) < 1e-5
    same, _ = clip_by_global_norm(g, 20.0)
    assert_allclose(same["a"], g["a"])


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110)
    s = lambda t: float(cosine_schedule(cfg, jnp.asarray(t)))
    assert s(0) == 0.0
    assert abs(s(10) - 1.0) < 1e-6
    assert s(60) < s(10)
    assert s(110) < 1e-6
    # warmup is linear
    assert abs(s(5) - 0.5) < 1e-6


def test_adamw_moment_dtype_bf16():
    cfg = AdamWConfig(moment_dtype="bfloat16")
    params = {"w": jnp.zeros((8, 8))}
    opt = adamw_init(params, cfg)
    assert opt["m"]["w"].dtype == jnp.bfloat16
    g = {"w": jnp.ones((8, 8))}
    _, opt2, _ = adamw_update(g, opt, params, cfg)
    assert opt2["m"]["w"].dtype == jnp.bfloat16


def test_adamw_bf16_params_matches_fp32():
    """bf16 storage params + fp32 master track the fp32 reference run
    closely (master bootstraps from the bf16 copy on step 1)."""
    import jax.numpy as jnp
    tgt = jnp.asarray([[1.0, -2.0], [3.0, 0.5]])
    cfg32 = AdamWConfig(lr=0.05, warmup_steps=0, total_steps=300,
                        weight_decay=0.0, clip_norm=1e9)
    cfgbf = AdamWConfig(lr=0.05, warmup_steps=0, total_steps=300,
                        weight_decay=0.0, clip_norm=1e9, bf16_params=True)
    from repro.optim.optimizer import cast_params_for_storage
    p32 = {"w": jnp.zeros((2, 2))}
    pbf = cast_params_for_storage({"w": jnp.zeros((2, 2))}, cfgbf)
    assert pbf["w"].dtype == jnp.bfloat16
    o32, obf = adamw_init(p32, cfg32), adamw_init(pbf, cfgbf)
    assert "master" in obf and obf["master"]["w"].dtype == jnp.float32
    loss = lambda p: jnp.sum((p["w"].astype(jnp.float32) - tgt) ** 2)
    for _ in range(150):
        p32, o32, _ = adamw_update(jax.grad(loss)(p32), o32, p32, cfg32)
        pbf, obf, _ = adamw_update(jax.grad(loss)(pbf), obf, pbf, cfgbf)
    assert pbf["w"].dtype == jnp.bfloat16
    assert float(loss(p32)) < 1e-3
    assert float(loss(pbf)) < 1e-2   # bf16 working copy: slightly looser
    # master tracks the fp32 trajectory closely
    assert float(jnp.abs(obf["master"]["w"] - p32["w"]).max()) < 0.05


def test_weight_decay_matrices_only():
    cfg = AdamWConfig(lr=0.1, weight_decay=1.0, warmup_steps=0,
                      clip_norm=1e9)
    params = {"mat": jnp.ones((2, 2)), "bias": jnp.ones((2,))}
    opt = adamw_init(params, cfg)
    zeros = jax.tree.map(jnp.zeros_like, params)
    p2, _, _ = adamw_update(zeros, opt, params, cfg)
    assert float(jnp.abs(p2["mat"] - 1.0).max()) > 1e-3   # decayed
    assert_allclose(p2["bias"], params["bias"])            # not decayed


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_dataset_determinism_and_skip_ahead():
    ds = TokenDataset(vocab=100, seq_len=8, global_batch=4, seed=7)
    b1 = ds.batch(13)
    ds2 = TokenDataset(vocab=100, seq_len=8, global_batch=4, seed=7)
    b2 = ds2.batch(13)   # fresh instance, direct skip-ahead
    np.testing.assert_array_equal(b1["inputs"], b2["inputs"])
    np.testing.assert_array_equal(b1["labels"], b2["labels"])
    assert not np.array_equal(ds.batch(14)["inputs"], b1["inputs"])
    # labels are the shifted continuation of inputs
    assert b1["inputs"].shape == (4, 8)


def test_dataset_token_file(tmp_path):
    toks = np.arange(1000, dtype=np.uint32)
    f = tmp_path / "tokens.bin"
    toks.tofile(f)
    ds = TokenDataset(vocab=2000, seq_len=16, global_batch=2, seed=0,
                      token_file=str(f))
    b = ds.batch(0)
    # shifted-by-one labels
    np.testing.assert_array_equal(b["labels"][:, :-1], b["inputs"][:, 1:])


def test_prefetcher():
    ds = TokenDataset(vocab=100, seq_len=8, global_batch=2, seed=0)
    pf = Prefetcher(ds, start_step=5, depth=2)
    b = next(pf)
    np.testing.assert_array_equal(b["inputs"], ds.batch(5)["inputs"])
    b2 = next(pf)
    np.testing.assert_array_equal(b2["inputs"], ds.batch(6)["inputs"])
    pf.close()


def test_dataset_embed_stub():
    ds = TokenDataset(vocab=100, seq_len=8, global_batch=2, seed=0,
                      embed_dim=32)
    b = ds.batch(0)
    assert b["inputs"].shape == (2, 8, 32)
    assert b["labels"].shape == (2, 8)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree(seed=0):
    r = np.random.default_rng(seed)
    return {"params": {"w": jnp.asarray(r.normal(size=(4, 4)), jnp.float32),
                       "b": jnp.asarray(r.normal(size=(4,)), jnp.float32)},
            "opt": {"count": jnp.asarray(3, jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path)
    t = _tree()
    ckpt.save(d, 10, t)
    assert ckpt.latest_step(d) == 10
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    out = ckpt.restore(d, 10, like)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_last(tmp_path):
    d = str(tmp_path)
    for s in (1, 2, 3, 4, 5):
        ckpt.save(d, s, _tree(s), keep_last=2)
    assert sorted(ckpt.available_steps(d)) == [4, 5]
    assert ckpt.latest_step(d) == 5


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    assert not [n for n in os.listdir(d) if n.endswith(".tmp")]


def test_async_checkpointer(tmp_path):
    d = str(tmp_path)
    c = ckpt.AsyncCheckpointer(d, keep_last=3)
    t = _tree()
    c.save_async(7, t)
    c.wait()
    assert ckpt.latest_step(d) == 7
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    out = ckpt.restore(d, 7, like)
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(t["params"]["w"]))


def test_restore_shape_mismatch_raises(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    bad = {"params": {"w": jax.ShapeDtypeStruct((5, 5), jnp.float32),
                      "b": jax.ShapeDtypeStruct((4,), jnp.float32)},
           "opt": {"count": jax.ShapeDtypeStruct((), jnp.int32)}}
    with pytest.raises(ValueError):
        ckpt.restore(d, 1, bad)


def _truncate_leaf(d, step, nbytes=16):
    p = os.path.join(d, f"step_{step}", "leaf_0.npy")
    with open(p, "r+b") as f:
        f.truncate(nbytes)


def _like(t):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)


def test_latest_step_skips_truncated(tmp_path):
    """A leaf truncated by a disk-full crash: latest_step warns and
    returns the newest INTACT step instead of the torn one."""
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1))
    ckpt.save(d, 2, _tree(2))
    _truncate_leaf(d, 2)
    with pytest.warns(RuntimeWarning, match="step_2"):
        assert ckpt.latest_step(d) == 1
    # torn manifest counts as corrupt too
    with open(os.path.join(d, "step_1", "manifest.json"), "w") as f:
        f.write('{"step": 1, "leav')
    with pytest.warns(RuntimeWarning):
        assert ckpt.latest_step(d) is None


def test_restore_falls_back_to_intact(tmp_path):
    d = str(tmp_path)
    t1, t2 = _tree(1), _tree(2)
    ckpt.save(d, 1, t1)
    ckpt.save(d, 2, t2)
    _truncate_leaf(d, 2)
    with pytest.warns(RuntimeWarning, match="step_1"):
        out = ckpt.restore(d, 2, _like(t2))
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(t1["params"]["w"]))
    # callers that need the exact step can refuse the fallback
    with pytest.raises(RuntimeError, match="truncated"):
        ckpt.restore(d, 2, _like(t2), fallback=False)


def test_restore_no_intact_step_raises(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 3, _tree())
    _truncate_leaf(d, 3)
    with pytest.raises(FileNotFoundError):
        ckpt.restore(d, 3, _like(_tree()))


def test_latest_step_unreadable_pointer(tmp_path):
    """A garbage LATEST pointer warns and falls back to the newest
    intact step directory rather than crashing the restart."""
    d = str(tmp_path)
    ckpt.save(d, 4, _tree())
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("not-a-step")
    with pytest.warns(RuntimeWarning, match="LATEST"):
        assert ckpt.latest_step(d) == 4
    assert ckpt.step_intact(d, 4)
    assert not ckpt.step_intact(d, 99)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_int8_quantize_bound(rng):
    x = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    q, scale = compression.quantize_int8(x)
    deq = compression.dequantize_int8(q, scale)
    assert float(jnp.abs(x - deq).max()) <= float(scale) / 2 + 1e-7


def test_error_feedback_unbiased_over_steps(rng):
    """With error feedback, the accumulated quantization error stays
    bounded (it does not grow with steps) -- the 1-bit-Adam property."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("pod",))
    g = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    err = jnp.zeros_like(g)
    f = jax.shard_map(
        lambda gg, ee: compression.compressed_psum(gg, "pod", ee),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    total_true, total_sent = jnp.zeros_like(g), jnp.zeros_like(g)
    for _ in range(50):
        out, err = f(g, err)
        total_true += g
        total_sent += out
    # cumulative transmitted == cumulative true up to one quantization step
    resid = jnp.abs(total_true - total_sent).max()
    _, scale = compression.quantize_int8(g)
    assert float(resid) < 3 * float(scale)


def test_lion_converges_quadratic():
    from repro.optim.optimizer import LionConfig, lion_init, lion_update
    import jax.numpy as jnp
    cfg = LionConfig(lr=0.05, warmup_steps=0, total_steps=400,
                     weight_decay=0.0, clip_norm=1e9)
    target = jnp.asarray([[1.0, -2.0], [0.5, 3.0]])
    params = {"w": jnp.zeros((2, 2))}
    opt = lion_init(params, cfg)
    assert set(opt) == {"m", "count"}   # one moment: half of Adam's state
    loss = lambda p: jnp.sum((p["w"] - target) ** 2)
    for _ in range(400):
        g = jax.grad(loss)(params)
        params, opt, metrics = lion_update(g, opt, params, cfg)
    # sign-update optimizer oscillates within +-lr of the optimum
    assert float(jnp.abs(params["w"] - target).max()) < 0.15
    assert bool(jnp.isfinite(metrics["grad_norm"]))
