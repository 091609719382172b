"""Multi-device tests: run in a subprocess with 8 forced host devices so
the main test process keeps the default single CPU device (the dry-run's
512-device setting is likewise process-local).

Covers: sharding-rule inference on a real mesh, sharded train step
numerics vs single-device, the GPipe ppermute pipeline, elastic-mesh
resharding restore, a miniature dry-run (lower+compile with in/out
shardings), and the conv stack (DESIGN.md Sec. 2.9): the structural
4-D conv-filter rule on real CNN/GAN trees, the batch_pspec size guard,
CNN/GAN train-step parity through the shard_map conv dispatch layer,
the plan-tiles-sees-local-shapes contract, and the
one-pallas_call-per-shard structural pin.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH="src",
           JAX_PLATFORMS="cpu")


def _run(body: str, timeout=600):
    code = textwrap.dedent(body)
    p = subprocess.run([sys.executable, "-c", code], env=ENV,
                       capture_output=True, text=True, timeout=timeout,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


def test_sharding_rules_on_mesh():
    _run("""
    import jax, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.parallel import sharding as sh

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    # FSDP+TP rule: (D, F) weight shards (fsdp, tp)
    spec = sh.leaf_pspec("blocks/mlp/wi", (64, 128), mesh)
    assert spec == P("data", "model"), spec
    # divisibility guard: odd dim stays unsharded
    spec = sh.leaf_pspec("blocks/mlp/wi", (63, 128), mesh)
    assert spec == P(None, "model"), spec
    # expert dim over model axis (EP)
    spec = sh.leaf_pspec("blocks/moe/experts_wi", (8, 64, 128), mesh)
    assert spec == P("model", "data", None), spec
    # vocab sharding
    spec = sh.leaf_pspec("embed/tok", (512, 64), mesh)
    assert spec == P("model", "data"), spec
    # scalars/norms replicated (P() and P(None) are equivalent)
    spec = sh.leaf_pspec("final_norm/scale", (64,), mesh)
    assert spec in (P(), P(None)), spec
    # leading scan dim stays unsharded
    spec = sh.leaf_pspec("blocks/attn/wq", (4, 64, 128), mesh)
    assert spec == P(None, "data", "model"), spec
    print("ok")
    """)


def test_sharded_train_step_matches_single_device():
    _run("""
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from repro.configs import get_smoke_config
    from repro.launch.steps import make_train_step
    from repro.models.lm import LM
    from repro.optim.optimizer import AdamWConfig, adamw_init
    from repro.parallel import sharding as sh

    cfg = get_smoke_config("qwen2_1_5b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=0)
    opt = adamw_init(params, ocfg)
    rng = np.random.default_rng(0)
    batch = {"inputs": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)),
                                   jnp.int32)}
    step = make_train_step(cfg, ocfg)
    p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    with mesh, sh.use_mesh(mesh):
        p_sh = sh.tree_shardings(params, mesh)
        o_sh = sh.tree_shardings(opt, mesh)
        params_s = jax.device_put(params, p_sh)
        opt_s = jax.device_put(opt, o_sh)
        batch_s = jax.device_put(batch, NamedSharding(
            mesh, sh.batch_pspec(mesh, 2, 0, 8)))
        p_out, _, m_out = jax.jit(step, in_shardings=(p_sh, o_sh, None),
                                  out_shardings=(p_sh, o_sh, None))(
            params_s, opt_s, batch_s)
    la, lb = float(m_out["loss"]), float(m_ref["loss"])
    assert abs(la - lb) / max(abs(lb), 1.0) < 1e-3, (la, lb)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_out)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=2e-2, atol=2e-2)
    print("ok")
    """)


def test_gpipe_pipeline_matches_sequential():
    _run("""
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.parallel.pipeline import gpipe

    n_stages, n_micro, mb, d = 4, 8, 2, 16
    mesh = Mesh(np.asarray(jax.devices()[:n_stages]).reshape(n_stages),
                ("stage",))
    rng = np.random.default_rng(0)
    Ws = jnp.asarray(rng.normal(size=(n_stages, d, d)) / np.sqrt(d),
                     jnp.float32)
    x = jnp.asarray(rng.normal(size=(n_micro, mb, d)), jnp.float32)

    def stage_fn(W, h):
        return jnp.tanh(h @ W)

    y = gpipe(mesh, "stage", stage_fn, Ws, x, n_micro)
    # sequential reference
    ref = x
    for s in range(n_stages):
        ref = jnp.tanh(ref @ Ws[s])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
    print("ok")
    """)


def test_elastic_restore_across_meshes():
    _run("""
    import jax, numpy as np, jax.numpy as jnp, tempfile
    from jax.sharding import Mesh, NamedSharding
    from repro.configs import get_smoke_config
    from repro.models.lm import LM
    from repro.parallel import sharding as sh
    from repro.train import checkpoint as ckpt
    from repro.train.fault_tolerance import elastic_mesh, survivors

    cfg = get_smoke_config("gemma_2b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    mesh8 = Mesh(np.asarray(jax.devices()).reshape(4, 2),
                 ("data", "model"))
    params8 = jax.device_put(params, sh.tree_shardings(params, mesh8))
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, {"params": params8})
        # two "hosts" of 4 devices; host 1 fails -> 4 survivors
        surv = survivors(mesh8, [1], devices_per_host=4)
        assert len(surv) == 4
        mesh4 = elastic_mesh(surv, model_parallel=2)
        assert mesh4.devices.size == 4
        like = {"params": jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)}
        shard4 = {"params": sh.tree_shardings(params, mesh4)}
        out = ckpt.restore(d, 1, like, shard4)
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(out["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("ok")
    """)


def test_elastic_mesh_non_power_of_two_survivors():
    """Losing 2 of 8 devices leaves 6: the TP axis halves until it
    divides the survivor count (16 -> 2 here, keeping TP a divisor of
    the original power-of-two layout), and every survivor is used."""
    _run("""
    import jax
    from repro.train.fault_tolerance import elastic_mesh, survivors
    from jax.sharding import Mesh
    import numpy as np

    devs = jax.devices()
    assert len(devs) == 8
    m6 = elastic_mesh(devs[:6], model_parallel=16)
    assert m6.shape["model"] == 2 and m6.shape["data"] == 3
    assert m6.devices.size == 6
    # 5 survivors: no even split exists, TP collapses to 1 (pure DP)
    m5 = elastic_mesh(devs[:5], model_parallel=4)
    assert m5.shape["model"] == 1 and m5.shape["data"] == 5
    # mp already divides: unchanged
    m8 = elastic_mesh(devs, model_parallel=4)
    assert m8.shape["model"] == 4 and m8.shape["data"] == 2
    # mp larger than the whole device set halves down into range
    m_big = elastic_mesh(devs[:6], model_parallel=64)
    assert m_big.shape["model"] == 2 and m_big.shape["data"] == 3
    # survivors() on a multi-host mesh: drop host 0 of 4x2-hosts
    mesh8 = Mesh(np.asarray(devs).reshape(4, 2), ("data", "model"))
    surv = survivors(mesh8, [0], devices_per_host=2)
    assert len(surv) == 6
    assert all(d.id >= 2 for d in surv)
    print("ok")
    """)


def test_mini_dryrun_lower_compile():
    """A miniature of the production dry-run: lower+compile a smoke arch
    on a (4,2) mesh with the exact production sharding logic, then check
    collectives exist in the HLO."""
    out = _run("""
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_smoke_config
    from repro.launch.steps import lower_cell
    from repro.launch import dryrun
    from repro.models.config import ShapeConfig
    import repro.launch.mesh as M

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2),
                ("data", "model"))
    cfg = get_smoke_config("qwen3_moe_235b_a22b")
    shape = ShapeConfig("mini_train", 64, 8, "train")
    lowered = lower_cell(cfg, shape, mesh)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes > 0
    colls = dryrun.parse_collectives(compiled.as_text())
    total = sum(v["count"] for k, v in colls.items() if k != "group_sizes")
    assert total > 0, colls
    print("collectives:", total)

    shape_d = ShapeConfig("mini_decode", 64, 8, "decode")
    lowered = lower_cell(cfg, shape_d, mesh)
    lowered.compile()
    print("ok")
    """)
    assert "ok" in out


def test_serve_sharding_and_cache_rules():
    _run("""
    import jax, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.parallel import sharding as sh
    from repro.launch.steps import cache_pspecs

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    # serve mode: plain matrices fold data into tp
    spec = sh.leaf_pspec("blocks/mlp/wi", (64, 128), mesh, serve=True)
    assert spec == P(None, ("model", "data")), spec
    spec = sh.leaf_pspec("blocks/mlp/wo", (128, 64), mesh, serve=True)
    assert spec == P(("model", "data"), None), spec
    # experts: E over model, FFN over data -- fully resident
    spec = sh.leaf_pspec("blocks/moe/experts_wi", (8, 64, 128), mesh,
                         serve=True)
    assert spec == P("model", None, "data"), spec
    # moe_ffn_data train variant
    spec = sh.leaf_pspec("blocks/moe/experts_wi", (8, 64, 128), mesh,
                         moe_ffn_data=True)
    assert spec == P("model", None, "data"), spec
    # KV cache: batch over data, SEQUENCE over model (flash-decoding)
    import jax.numpy as jnp
    cache = {"k": jax.ShapeDtypeStruct((2, 8, 64, 4, 16), jnp.bfloat16),
             "v": jax.ShapeDtypeStruct((2, 8, 64, 4, 16), jnp.bfloat16),
             "len": jax.ShapeDtypeStruct((), jnp.int32)}
    specs = cache_pspecs(cache, mesh)
    assert specs["k"] == P(None, "data", "model", None, None), specs["k"]
    print("ok")
    """)


def test_decode_lowering_has_no_cache_gather():
    """The Perf A1 fix at test scale: decode lowers with the cache
    sharded and without whole-cache all-gathers."""
    _run("""
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_smoke_config
    from repro.launch.steps import lower_cell
    from repro.launch import dryrun
    from repro.models.config import ShapeConfig

    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                ("data", "model"))
    cfg = get_smoke_config("gemma_7b").scaled(attn_chunk=32)
    shape = ShapeConfig("mini_decode", 64, 8, "decode")
    compiled = lower_cell(cfg, shape, mesh).compile()
    colls = dryrun.parse_collectives(compiled.as_text())
    # cache (layers, B, 64, H, D) bf16: a whole-cache gather would move
    # >= L*B*S*H*D*2 bytes; assert total gather volume stays well below.
    import math
    cache_bytes = cfg.n_layers * 8 * 64 * cfg.n_kv_heads * \
        cfg.head_dim * 2 * 2
    assert colls["all-gather"]["bytes"] < cache_bytes, \
        (colls["all-gather"], cache_bytes)
    print("ok")
    """)


def test_compressed_allreduce_across_pods():
    _run("""
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.parallel.compression import compressed_psum

    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("pod", "data"))
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)  # per-pod grads
    e = jnp.zeros_like(g)
    f = jax.shard_map(lambda gg, ee: compressed_psum(gg, "pod", ee),
                      mesh=mesh, in_specs=(P("pod"), P("pod")),
                      out_specs=(P("pod"), P("pod")))
    out, err = f(g, e)
    want = g.mean(axis=0)
    # each pod's shard now holds (approximately) the mean
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=0.15, atol=0.05)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out[1]),
                               rtol=1e-6, atol=1e-6)
    print("ok")
    """)


# ---------------------------------------------------------------------------
# Conv stack: shard_map dispatch layer + conv-filter sharding rules
# ---------------------------------------------------------------------------


def test_conv_filter_sharding_rules():
    """The structural rank-4 rule: real CNN/GAN param trees get
    non-trivial conv-filter PartitionSpecs (the old behavior -- list
    indices / GAN layer names falling to the replicate-all catch-all --
    would leave every one of them P())."""
    _run("""
    import jax, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.models import cnn, gan
    from repro.parallel import sharding as sh

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    params = cnn.simple_cnn_init(jax.random.PRNGKey(0), in_ch=3,
                                 widths=(32, 64, 128), n_classes=10)
    specs = sh.tree_pspecs(params, mesh)
    # Cin=3 stem: fsdp(4) does not divide 3 -> Cin stays unsharded, but
    # Cout=32 shards over tp
    assert specs["convs"][0] == P(None, None, None, "model"), specs
    # interior filters: full (.., Cin@fsdp, Cout@tp)
    assert specs["convs"][1] == P(None, None, "data", "model"), specs
    assert specs["convs"][2] == P(None, None, "data", "model"), specs
    # the 2-D head still follows its name rule, not the conv rule
    assert specs["head"] == P("data", "model"), specs

    g = gan.generator_init(jax.random.PRNGKey(1), z_dim=64, base=64)
    d = gan.discriminator_init(jax.random.PRNGKey(2), in_ch=3, base=64)
    gs, ds = sh.tree_pspecs(g, mesh), sh.tree_pspecs(d, mesh)
    assert gs["t1"] == P(None, None, "data", "model"), gs
    assert gs["t2"] == P(None, None, "data", "model"), gs
    # t3 has Cin=3 (the RGB output side of the tconv): guard drops fsdp
    assert gs["t3"] == P(None, None, None, "model"), gs
    assert ds["c2"] == P(None, None, "data", "model"), ds
    # serve layout: conv filters fully sharded over model+data on Cout
    gss = sh.tree_pspecs(g, mesh, serve=True)
    assert gss["t1"] == P(None, None, None, ("model", "data")), gss
    # the depthwise (K, C) name rule is untouched by the structural rule
    spec = sh.leaf_pspec("blocks/conv_w", (4, 64), mesh)
    assert spec == P(None, "model"), spec
    print("ok")
    """)


def test_batch_pspec_requires_size():
    """batch_pspec only shards when the batch size is known AND divides
    the dp axes -- an unknown (None) or ragged size stays unsharded."""
    _run("""
    import jax, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.parallel import sharding as sh

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    # divisible batch: sharded over the data axes
    assert sh.batch_pspec(mesh, 4, 0, 8) == P("data", None, None, None)
    # unknown size: UNSHARDED (the old code sharded unconditionally and
    # a ragged last batch then failed to lower)
    assert sh.batch_pspec(mesh, 4, 0, None) == P(None, None, None, None)
    # ragged size: guard drops the axis
    assert sh.batch_pspec(mesh, 2, 0, 6) == P(None, None)
    print("ok")
    """)


def test_sharded_cnn_sgd_step_matches_single_device():
    """Tentpole numerics: the CNN SGD step on the pallas backend, 8 fake
    devices FSDP+TP vs single device, same seed -> same params."""
    _run("""
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from repro.models import cnn
    from repro.parallel import sharding as sh

    params = cnn.simple_cnn_init(jax.random.PRNGKey(0), in_ch=3,
                                 widths=(8, 16), n_classes=10)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 12, 12, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=8))
    step = lambda p, x_: cnn.sgd_step(p, x_, labels, lr=0.05, stride=2,
                                      backend="pallas", fuse_epilogue=True)
    p_ref, loss_ref = jax.jit(step)(params, x)

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    with mesh, sh.use_mesh(mesh):
        psh = sh.tree_shardings(params, mesh)
        p_s = jax.device_put(params, psh)
        x_s = jax.device_put(x, NamedSharding(
            mesh, sh.batch_pspec(mesh, 4, 0, 8)))
        p_out, loss = jax.jit(step)(p_s, x_s)
    assert abs(float(loss) - float(loss_ref)) < 1e-5, (loss, loss_ref)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    print("ok")
    """)


def test_sharded_gan_gen_step_matches_single_device():
    """Tentpole numerics for the GAN side: generator SGD step (zero-free
    tconv forward + fused ct-backward) under the 8-device mesh."""
    _run("""
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from repro.models import gan
    from repro.parallel import sharding as sh

    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    gp = gan.generator_init(k1, z_dim=16, base=8, out_ch=3)
    dp = gan.discriminator_init(k2, in_ch=3, base=8)
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    step = lambda g, z_: gan.gen_sgd_step(g, dp, z_, lr=0.05,
                                          backend="pallas",
                                          fuse_epilogue=True)
    g_ref, loss_ref = jax.jit(step)(gp, z)

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    with mesh, sh.use_mesh(mesh):
        g_s = jax.device_put(gp, sh.tree_shardings(gp, mesh))
        z_s = jax.device_put(z, NamedSharding(
            mesh, sh.batch_pspec(mesh, 2, 0, 8)))
        g_out, loss = jax.jit(step)(g_s, z_s)
    assert abs(float(loss) - float(loss_ref)) < 1e-5, (loss, loss_ref)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    print("ok")
    """)


def test_plan_tiles_under_shard_map_sees_local_shapes():
    """The local-shapes contract (DESIGN.md Sec. 2.9): inside the
    shard_map body the kernels resolve `tiling.plan_tiles` from traced
    LOCAL block shapes -- batch/dp and channel/tp already divided out --
    so the planner's Cin/Cout tiles are the per-shard geometry."""
    _run("""
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.conv import ecoflow_conv
    from repro.core.spec import Epilogue
    from repro.kernels import tiling
    from repro.parallel import sharding as sh

    seen = []
    orig = tiling.plan_tiles
    def spy(op, spec, **kw):
        seen.append((op, tuple(kw["x_shape"]), tuple(kw["dy_shape"])))
        return orig(op, spec, **kw)
    tiling.plan_tiles = spy

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    B, N, Ci, Co = 8, 10, 4, 8
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, Ci, Co)), jnp.float32)
    ep = Epilogue(activation="relu")

    def loss(x_, w_):
        return ecoflow_conv(x_, w_, 2, 1, "pallas", epilogue=ep).sum()

    with mesh, sh.use_mesh(mesh):
        jax.grad(loss, argnums=(0, 1))(x, w)

    assert seen, "plan_tiles was never consulted"
    for op, xs, dys in seen:
        # batch divided by |dp|=4, Cout by |tp|=2; Ci=4 is the full Cin
        # (contracted dim -- never sharded on the forward path)
        assert xs[0] == B // 4, (op, xs)
        assert xs[3] == Ci, (op, xs)
        assert dys[0] == B // 4, (op, dys)
        assert dys[3] == Co // 2, (op, dys)
    print("ok", sorted({op for op, _, _ in seen}))
    """)


def test_conv_layer_single_launch_per_shard():
    """Structural pin: under the mesh one conv layer's forward+backward
    jaxpr contains exactly TWO pallas_calls (one fused forward launch,
    one fused dual-gradient backward launch), each inside a shard_map
    body, with the explicit dx/dW/db psums alongside -- and none outside
    any shard_map."""
    _run("""
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.conv import ecoflow_conv
    from repro.core.spec import Epilogue
    from repro.parallel import sharding as sh

    def subjaxprs(eqn):
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                yield v.jaxpr
            elif hasattr(v, "eqns"):
                yield v

    def walk(jaxpr, skip_shard_map=False):
        for e in jaxpr.eqns:
            yield e
            if skip_shard_map and e.primitive.name == "shard_map":
                continue
            for sub in subjaxprs(e):
                yield from walk(sub, skip_shard_map)

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 10, 10, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 8)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    ep = Epilogue(activation="relu", bias=True)

    def loss(x_, w_, b_):
        return ecoflow_conv(x_, w_, 2, 1, "pallas", bias=b_,
                            epilogue=ep).sum()

    with mesh, sh.use_mesh(mesh):
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b)

    names = [e.primitive.name for e in walk(jaxpr.jaxpr)]
    assert names.count("pallas_call") == 2, names
    assert names.count("shard_map") == 2, names
    assert names.count("psum") >= 3, names   # dx@tp, dW@dp, db@dp
    outside = [e.primitive.name
               for e in walk(jaxpr.jaxpr, skip_shard_map=True)]
    assert outside.count("pallas_call") == 0, outside
    print("ok")
    """)
