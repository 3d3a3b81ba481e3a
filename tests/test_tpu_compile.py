"""The serving path's device programs compile for a TPU v5e.

No chip is attached: the fixture describes a ``v5e:2x2`` topology and
each test compiles one program at its real width, so what the chip's
compiler would refuse (an unaligned slice, a program over the device's
memory, an unpartitionable sharding) fails here at no chip time. Nothing
runs, so nothing here is a result or a time. The topology is described
only inside the fixture — never while a module is imported — and these
tests stay in this one file (see the on-chip-measurement guide, §2).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from emqx_tpu.models import router_model as rm
from emqx_tpu.ops import trie_match as tm
from emqx_tpu.parallel import mesh as pmesh

HBM_BYTES = 16 * 2**30    # one v5e chip
B, L = 16384, 16          # LANE_MAX_BATCH topics, max_levels words
K, M, RET_CAP, PROBES = 32, 128, 16, 8
POOL = (64, 128)          # dense-pool rows × bitmap words (4096 slots)
# BASELINE config 2, as the 1M-filter fleet builds it: edge table at
# <=25% load, 1.5x node headroom, rowmap at 1.5x live fids
FLAT = dict(H=2**24, N=3_600_000, F=2**21)
# BASELINE config 3 over S=4 shards: each shard holds 10M/4 filters
SHARDED = dict(S=4, H=2**25, N=9_000_000, F=2**24)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from the
    # persistent cache, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flat_tables(sharding):
    H, N, F = FLAT["H"], FLAT["N"], FLAT["F"]
    trie = tm.DeviceTrie(*(_spec((H,), sharding) for _ in range(3)),
                         *(_spec((N,), sharding) for _ in range(3)))
    return (trie, _spec((F,), sharding),
            _spec(POOL, sharding, jnp.uint32))


def _per_device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_router_step_compiles_at_1m_width(one_chip):
    step = jax.jit(functools.partial(
        rm.router_step, K=K, M=M, max_probes=PROBES, ret_cap=RET_CAP,
        with_counters=True))
    compiled = step.lower(
        *_flat_tables(one_chip), _spec((B, L), one_chip),
        _spec((B,), one_chip), _spec((B,), one_chip, jnp.bool_)).compile()
    assert _per_device_bytes(compiled) < HBM_BYTES


def test_router_step_sharded_compiles_on_4_chips(topo):
    S, H, N, F = (SHARDED[k] for k in ("S", "H", "N", "F"))
    mesh = Mesh([topo.devices[:4]], (pmesh.DP, pmesh.TP))   # dp=1, tp=4
    sh = pmesh.router_shardings(mesh)
    trie = tm.DeviceTrie(
        *(_spec((S, H), sh["trie_sub"]) for _ in range(3)),
        *(_spec((S, N), sh["trie_sub"]) for _ in range(3)))
    step = jax.jit(functools.partial(
        rm.router_step_sharded, n_shards=S, K=K, M=M, max_probes=PROBES,
        ret_cap=RET_CAP, shardings=sh, with_counters=True))
    compiled = step.lower(
        trie, _spec((F,), sh["replicated"]),
        _spec(POOL, sh["bitmaps"], jnp.uint32),
        _spec((B, L), sh["batch_dp"]), _spec((B,), sh["batch_dp"]),
        _spec((B,), sh["batch_dp"], jnp.bool_)).compile()
    assert _per_device_bytes(compiled) < HBM_BYTES
    # each chip holds a quarter of the trie, not all of it
    trie_bytes = 4 * S * (3 * H + 3 * N)
    assert compiled.memory_analysis().argument_size_in_bytes < trie_bytes / 2
    assert "all-gather" in compiled.as_text()


def test_apply_patches_compiles_at_top_bucket(one_chip):
    cap = rm.PATCH_BUCKETS[-1]
    vec = lambda dtype=jnp.int32: _spec((cap,), one_chip, dtype)  # noqa: E731
    tupd = {name: (vec(), vec()) for name in tm.DeviceTrie._fields}
    compiled = rm._apply_patches.lower(
        *_flat_tables(one_chip), tupd, (vec(), vec()),
        (vec(), vec(), vec(jnp.uint32))).compile()
    assert _per_device_bytes(compiled) < HBM_BYTES
    # the donated tables are updated in place, not copied
    assert compiled.memory_analysis().alias_size_in_bytes > 0
