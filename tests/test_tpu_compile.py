"""Compile the main path's kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at real widths and compiles it
with the TPU compiler for a ``v5e:2x2`` topology that is described, not
attached. That catches what interpret mode cannot — tiling, VMEM and
memory limits, and shardings that do not partition. The topology is
described inside a fixture, never while a module is imported, so every
test worker collects the same tests and only the one given this file
loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.sim.engines import jax_backend as jb

ROWS = 100_000          # a 4 x 25,000-tenant stream fleet
SECONDS = 120           # one round interval of the stream fleet


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("data",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def test_flash_attention_tinyllama(one_chip):
    cfg = get_config("tinyllama-1.1b")
    S = 512
    q = _sds((1, cfg.num_heads, S, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _sds((1, cfg.num_kv_heads, S, cfg.head_dim), jnp.bfloat16,
              one_chip)
    compiled = ops.flash_attention.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_tinyllama(one_chip):
    cfg = get_config("tinyllama-1.1b")
    B, page, pool, max_pages = 8, 16, 512, 64      # 1,024 tokens a sequence
    q = _sds((B, cfg.num_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _sds((cfg.num_kv_heads, pool, page, cfg.head_dim), jnp.bfloat16,
              one_chip)
    table = _sds((B, max_pages), jnp.int32, one_chip)
    lengths = _sds((B,), jnp.int32, one_chip)
    compiled = ops.paged_attention.lower(q, kv, kv, table, lengths).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fleet_args(name, rows, L, sharding):
    keys = _sds((rows, 2), jnp.uint32, sharding)
    col = _sds((rows,), jnp.float32, sharding)
    if name == "dense":
        return (functools.partial(jb._dense_impl, L), keys,
                _sds((rows, L), jnp.bool_, sharding), col, col, col)
    if name == "fused":
        return (functools.partial(jb._fused_impl, L), keys,
                _sds((rows,), jnp.int32, sharding), col, col, col)
    return functools.partial(jb._jitter_impl, L), keys, col


@pytest.mark.parametrize("name,rows,L", [
    ("dense", ROWS, SECONDS),
    ("fused", ROWS, 1024),
    # a 1,000-tenant-per-node game fleet's padded requests per row, at
    # the row tile that keeps the call under the engine's cell budget
    ("jitter", 20_000, 6272),
])
def test_fleet_kernel(one_chip, name, rows, L):
    """The fleet step kernels at the rows one call of the engine gets."""
    assert rows * L <= jb._MAX_CELLS
    fn, *args = _fleet_args(name, rows, L, one_chip)
    _compile(fn, *args)


def test_fleet_kernel_sharded_over_four_chips(four_chips):
    """The jax engine's row sharding (``shard_map`` over "data") compiles
    for four chips, and each chip holds a quarter of the rows."""
    spec = P("data")
    fn, *args = _fleet_args("dense", ROWS, SECONDS,
                            NamedSharding(four_chips, spec))
    sharded = jax.shard_map(fn, mesh=four_chips, in_specs=(spec,) * 5,
                            out_specs=(spec,) * 4, check_vma=False)
    compiled = _compile(sharded, *args)
    for out in compiled.output_shardings:
        assert out.spec == spec and out.mesh.size == 4


def test_pallas_latency_scale(one_chip):
    col = _sds((ROWS,), jnp.float32, one_chip)
    demand = _sds((ROWS, SECONDS), jnp.float32, one_chip)
    compiled = jb._pallas_latency_scale.lower(
        col, col, demand, col, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
