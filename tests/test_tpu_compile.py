"""Compile the Pallas kernels for a described TPU v5e, with no chip attached.

Interpret mode (tests/test_kernels.py) checks what a kernel computes; only
the TPU compiler (Mosaic) checks that its blocks tile and fit. These tests
lower each kernel at the widths the main path runs it at — ``fleet_256``
(K=8, N=256) for ``gmsa_score``, plain and vmapped over Monte-Carlo runs as
``simulate_many`` calls it, the Facebook-4DC widths (K=1, N=4, 1000 runs)
for its runs-on-lanes kernel, and the mamba2-2.7b layer geometry for
``ssd_scan`` — and check that the compiled program holds the kernel. One
more test bounds the TPU compile time of the faulted placed engine, and one
pins the trace draws' lowering: a fused count on the TPU, the binary search
on the CPU.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gmsa_score import gmsa_score
from repro.kernels.ssd_scan import ssd_scan

K, N = 8, 256                                   # configs.fleet_256
RUNS = 32                                       # vmapped Monte-Carlo width
SSD = dict(b=1, s=1024, h=80, p=64, n=128)      # mamba2-2.7b layer geometry


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A described-device compile cannot be read back without the chip:
    keep it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_gmsa_score_compiles_for_v5e(one_chip, no_cache):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    text = _compiled_text(
        lambda *a: gmsa_score(*a, interpret=False),
        s((K, N)), s((K, N)), s((K,)), s((K,)), s((K, N, N)), s((N,)),
    )
    assert "tpu_custom_call" in text


def test_gmsa_score_vmapped_over_runs_compiles_for_v5e(one_chip, no_cache):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    per_run = jax.vmap(lambda *a: gmsa_score(*a, interpret=False),
                       in_axes=(0, 0, 0, None, None, 0))
    text = _compiled_text(
        per_run,
        s((RUNS, K, N)), s((RUNS, K, N)), s((RUNS, K)), s((K,)),
        s((K, N, N)), s((RUNS, N)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("r_runs", [False, True], ids=["shared_r", "r_runs"])
def test_gmsa_score_on_lanes_compiles_for_v5e(one_chip, no_cache, r_runs):
    """``facebook_4dc``'s dispatch vmapped over 1000 runs, r shared (the
    static-r policy) or per run (the placement controller's carried r):
    one kernel launch, named ``gmsa_score``."""
    k, n, runs = 1, 4, 1000
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    per_run = jax.vmap(lambda *a: gmsa_score(*a, interpret=False),
                       in_axes=(0, 0, 0, None, 0 if r_runs else None, None))
    text = _compiled_text(
        per_run,
        s((runs, k, n)), s((runs, k, n)), s((runs, k)), s((k,)),
        s(((runs,) if r_runs else ()) + (k, n, n)), s((n,)),
    )
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and '/gmsa_score/pallas_call"' in calls[0]


def test_placed_engine_compiles_for_v5e_in_seconds(one_chip, no_cache):
    """The faulted placed engine under the adaptive rule, as the four-chip
    phase of ``chip_smoke.py`` runs it. Its capacity projection once
    unrolled 32 steps per rule call, and the TPU compile took ~165 s."""
    import time

    import numpy as np

    from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
    from repro.core.gmsa import gmsa_policy
    from repro.placement import PlacementConfig, make_adaptive_rule
    from repro.placement.controller import simulate_placed_many
    from repro.traces.bandwidth import bandwidth_draw
    from repro.traces.faults import scheduled_failure_trace

    cfg = PaperSimConfig()
    _, build = make_sim_builder(cfg)
    up, down = bandwidth_draw(jax.random.key(0), cfg.n_sites)
    alive = np.asarray(scheduled_failure_trace(
        cfg.t_slots, cfg.n_sites, [(2, cfg.t_slots // 2, None)]))
    s = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    lowered = simulate_placed_many.lower(
        build, s(up), s(down), gmsa_policy, make_adaptive_rule(up), key, 8,
        PlacementConfig(epoch_slots=24, manager_share=cfg.manager_share),
        1.0, alive=s(alive))
    t0 = time.perf_counter()
    lowered.compile()
    assert time.perf_counter() - t0 < 60.0


def test_trace_draws_compile_to_one_count_for_v5e(one_chip, no_cache):
    """``facebook_4dc``'s per-run draws over 1000 runs, as ``simulate_many``
    builds them: on the TPU one compare-and-count with no loop, no gather
    and no materialised (runs, K+N·K, M+1, T) compare; on the CPU the
    binary search's loop."""
    from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder

    cfg = PaperSimConfig()
    _, build = make_sim_builder(cfg)
    draws = lambda keys: tuple(jax.vmap(build)(keys)[:2])
    key_dtype = jax.random.key(0).dtype
    runs = 1000
    compiled = jax.jit(draws).lower(
        jax.ShapeDtypeStruct((runs,), key_dtype, sharding=one_chip)).compile()
    loops = re.compile(r"\b(while|gather)\(")
    assert not loops.search(compiled.as_text())
    tables = cfg.k_types + cfg.n_sites * cfg.k_types
    compare_bytes = runs * tables * (int(cfg.a_max) + 1) * cfg.t_slots
    assert compiled.memory_analysis().temp_size_in_bytes < compare_bytes / 10

    cpu_text = jax.jit(draws).lower(
        jax.ShapeDtypeStruct((runs,), key_dtype)).compile().as_text()
    assert re.search(r"\bwhile\(", cpu_text)


def test_ssd_scan_compiles_for_v5e(one_chip, no_cache):
    b, s_len, h, p, n = (SSD[k] for k in "bshpn")
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    text = _compiled_text(
        lambda *a: ssd_scan(*a, chunk=128, interpret=False),
        s((b, s_len, h, p)), s((b, s_len, h)), s((h,)), s((b, s_len, n)),
        s((b, s_len, n)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["gmsa_score", "ssd_scan"])
def test_each_kernel_carries_its_name(one_chip, no_cache, kernel):
    """A device profile finds each kernel by the ``name=`` of its
    ``pallas_call``: the custom call and its ``op_name`` carry it."""
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    if kernel == "gmsa_score":
        text = _compiled_text(
            lambda *a: gmsa_score(*a, interpret=False),
            s((1, 4)), s((1, 4)), s((1,)), s((1,)), s((1, 4, 4)), s((4,)),
        )
    else:
        text = _compiled_text(
            lambda *a: ssd_scan(*a, chunk=128, interpret=False),
            s((1, 128, 2, 64)), s((1, 128, 2)), s((2,)), s((1, 128, 128)),
            s((1, 128, 128)),
        )
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert calls and all(f"%{kernel}." in line and f'/{kernel}/pallas_call"' in line
                         for line in calls)
