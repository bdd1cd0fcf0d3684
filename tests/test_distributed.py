"""Distributed-runtime tests.

Multi-device cases run in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps its single CPU device (the dry-run is the only place allowed
to fake 512 devices; see the assignment note).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.distributed.sharding import param_pspecs
from repro.launch.mesh import make_debug_mesh
from repro.models.lm import layer_param_specs, top_param_specs


def test_param_pspecs_cover_every_param():
    mesh = make_debug_mesh(1)
    for arch in C.list_archs():
        cfg = C.get_arch(arch, "smoke")
        specs = param_pspecs(cfg, mesh)
        assert set(specs["blocks"]) == set(layer_param_specs(cfg))
        assert set(specs) - {"blocks"} == set(top_param_specs(cfg))


def test_fallback_logged_for_indivisible_heads():
    """qwen2: 14 heads on a 16-way model axis must fall back to replication."""
    import jax as _jax
    mesh = _jax.make_mesh((1, 1), ("data", "model"))  # sizes 1: all shardable
    log: dict = {}
    cfg = C.get_arch("qwen2-0.5b")
    param_pspecs(cfg, mesh, log)
    assert "replicated_fallbacks" not in log  # axis size 1 always shards

    # Fake a 16-way model axis via divisibility check only.
    from repro.distributed.sharding import _shardable
    assert not _shardable("q_out", cfg, 16)
    assert not _shardable("kv_out", cfg, 16)
    assert _shardable("mlp", cfg, 16)
    assert _shardable("vocab", cfg, 16)


_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp, numpy as np
    import repro.configs as C
    from repro.configs.base import ShapeConfig
    from repro.models import init_params, init_cache
    from repro.models.inputs import make_batch, make_decode_tokens
    from repro.train.step import TrainStepConfig, make_train_step
    from repro.train.optimizer import adamw_init
    from repro.serve.step import make_decode_step
    from repro.launch.mesh import make_debug_mesh

    # The launcher's (2, 2, 2) mesh: Auto axes, placed by in/out shardings.
    mesh = make_debug_mesh(multi_pod=True)
    mesh_ctx = jax.set_mesh
    cfg = C.get_arch("qwen2-0.5b", "smoke")
    shape = ShapeConfig("t", "train", 64, 8)
    out = {}
    params_result = {}
    for sync in ["native", "int8"]:
        tcfg = TrainStepConfig(microbatches=2, remat="dots", grad_sync=sync)
        step, pspecs, opt_specs, shardings_for, init_efb = make_train_step(cfg, mesh, tcfg)
        batch = make_batch(cfg, shape, jax.random.key(0), embed_dtype=jnp.float32)
        with mesh_ctx(mesh):
            in_sh, out_sh = shardings_for(batch, shape.global_batch)
            params = jax.device_put(init_params(jax.random.key(1), cfg, jnp.float32), in_sh[0])
            opt = jax.device_put(adamw_init(params), in_sh[1])
            batchp = jax.device_put(batch, in_sh[2])
            efb = jax.device_put(init_efb(params), in_sh[3])
            jstep = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            p2, o2, metrics, efb2 = jstep(params, opt, batchp, efb)
            out[sync] = float(metrics["loss"])
            params_result[sync] = p2
    delta = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(params_result["native"]),
                        jax.tree.leaves(params_result["int8"]))
    )
    # sharded decode
    dshape = ShapeConfig("d", "decode", 128, 8)
    fn, pspecs, shardings_for = make_decode_step(cfg, mesh)
    with mesh_ctx(mesh):
        cache = init_cache(cfg, 8, 128, jnp.float32, prefilled=128)
        in_sh, out_sh = shardings_for(cache, 8)
        params = jax.device_put(init_params(jax.random.key(1), cfg, jnp.float32), in_sh[0])
        cache = jax.device_put(cache, in_sh[1])
        toks = jax.device_put(make_decode_tokens(cfg, dshape), in_sh[2])
        logits, _ = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)(params, cache, toks)
        decode_finite = bool(jnp.all(jnp.isfinite(logits)))
    print(json.dumps({"loss": out, "param_delta": delta, "decode_finite": decode_finite}))
""")


@pytest.mark.slow
def test_multidevice_train_and_decode_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True, text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=env, timeout=560,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    report = json.loads(res.stdout.strip().splitlines()[-1])
    # int8-compressed grads track native within quantization error.
    assert abs(report["loss"]["native"] - report["loss"]["int8"]) < 1e-3
    assert report["param_delta"] < 1e-4
    assert report["decode_finite"]


_MOE_MESH_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    import repro.configs as C
    from jax.sharding import get_abstract_mesh
    from repro.models.moe import moe_ffn, _moe_local, expert_capacity

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    mesh_ctx = jax.set_mesh
    cfg = C.get_arch("deepseek-moe-16b", "smoke")
    d, e = cfg.d_model, cfg.num_experts
    f = cfg.moe_d_ff or cfg.d_ff
    k = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(k[0], (4, 16, d), jnp.float32)
    wr = jax.random.normal(k[1], (d, e), jnp.float32) * 0.02
    wg = jax.random.normal(k[2], (e, d, f), jnp.float32) * 0.02
    wu = jax.random.normal(k[3], (e, d, f), jnp.float32) * 0.02
    wd = jax.random.normal(k[4], (e, f, d), jnp.float32) * 0.02

    y_ref, aux_ref = _moe_local(x, wr, wg, wu, wd, cfg, expert_capacity(16, cfg, 1.25))
    with mesh_ctx(mesh):
        ambient = not get_abstract_mesh().empty
        y, aux = jax.jit(lambda *a: moe_ffn(*a, cfg))(x, wr, wg, wu, wd)
    print(json.dumps({
        "ambient": ambient,
        "dy": float(jnp.max(jnp.abs(y - y_ref))),
        "daux": abs(float(aux) - float(aux_ref)),
    }))
""")


@pytest.mark.slow
def test_moe_manual_shard_map_path_live_subprocess():
    """The ambient mesh must be visible to moe_ffn, so its manual shard_map
    path (not the replicating fallback) runs — and agrees with the
    single-device reference."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _MOE_MESH_PROG],
        capture_output=True, text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=env, timeout=560,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    report = json.loads(res.stdout.strip().splitlines()[-1])
    assert report["ambient"], "get_abstract_mesh missed the ambient mesh"
    assert report["dy"] < 1e-5
    assert report["daux"] < 1e-6


def test_compression_roundtrip_single_pod():
    """n_pods=1 degenerate case: compressed sum == identity + residual."""
    from repro.distributed.compression import _dequantize, _quantize
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 256)).astype(np.float32)
    q, s = _quantize(jax.numpy.asarray(x))
    back = np.asarray(_dequantize(q, s))
    assert np.max(np.abs(back - x)) <= np.max(np.abs(x)) / 127 + 1e-6
