"""Compile the main path's kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler that ships with jax lowers each program for a
chip that is described (``v5e:2x2``) and not attached, and refuses what the
chip would refuse — a Mosaic primitive it cannot lower, a tile that overflows
VMEM.  Interpret-mode tests (``test_kernels.py``) cannot see either.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under xdist every
worker imports this file.  The persistent compilation cache is off around
these compiles, since an entry written for a described chip cannot be read
back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.compression import grad as gradc
from repro.core import jitmode
from repro.kernels import huffman
from repro.kernels.fastmode import ops as fops
from repro.kernels.lorenzo import ops as lops
from repro.kernels.transform import ops as tops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(no_cache):
    return SingleDeviceSharding(no_cache.devices[0])


@pytest.fixture(scope="module")
def data_mesh(no_cache):
    auto = (jax.sharding.AxisType.Auto,)
    return Mesh(np.asarray(no_cache.devices).reshape(-1), ("data",), axis_types=auto)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pallas_text(compiled) -> str:
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


@pytest.mark.parametrize("shape", [(1, 1 << 20), (512, 512), (1800, 3600), (1024, 8192)])
@pytest.mark.parametrize("mode", ["1d", "2d"])
def test_lorenzo_compiles(one_chip, shape, mode):
    x = _sds(shape, jnp.float32, one_chip)
    enc = lops.lorenzo_encode.lower(x, eb=1e-3, mode=mode, interpret=False).compile()
    _pallas_text(enc)
    d = _sds(shape, jnp.int32, one_chip)
    dec = lops.lorenzo_decode.lower(d, eb=1e-3, mode=mode, interpret=False).compile()
    _pallas_text(dec)


# a CESM-ATM field, and one of the six row chunks the auto contest cuts it in
@pytest.mark.parametrize("shape", [(1800, 3600), (300, 3600)])
def test_lorenzo_check_compiles(one_chip, shape):
    x = _sds(shape, jnp.float32, one_chip)
    d = _sds(shape, jnp.int32, one_chip)
    compiled = lops.lorenzo_check.lower(
        x, d, d, eb=1e-3, mode="2d", code_dtype=np.dtype(np.uint16)
    ).compile()
    codes, n, cand = compiled.out_info
    assert codes.shape == shape and np.dtype(codes.dtype) == np.uint16
    assert n.shape == () and cand.shape == (4, lops.CHECK_CAPACITY)


@pytest.mark.parametrize("shape", [(1, 1 << 20), (1800, 3600)])
@pytest.mark.parametrize("mode", ["1d", "2d"])
def test_transform_compiles(one_chip, shape, mode):
    x = _sds(shape, jnp.float32, one_chip)
    for fn in (tops.transform_fwd, tops.transform_inv):
        _pallas_text(fn.lower(x, mode=mode, interpret=False).compile())


def test_fastmode_stats_compile(one_chip):
    # 1800x3600 float32 in 256-element blocks, padded to the 256-row tile
    nb = -(-1800 * 3600 // 256 // 256) * 256
    x = _sds((nb, 256), jnp.float32, one_chip)
    _pallas_text(fops._stats_padded.lower(x, bm=256, interpret=False).compile())


# an auto chunk at 4.5 bits a code; a 1800x3600 field at 4 and at 16
@pytest.mark.parametrize("size,log2_words", [(1 << 20, 18), (13 << 19, 20), (13 << 19, 22)])
def test_huffman_pack_compiles(one_chip, size, log2_words):
    syms = _sds((size,), jnp.uint16, one_chip)
    table = _sds((huffman.TABLE_SIZE,), jnp.uint32, one_chip)
    meta = _sds((2,), jnp.int32, one_chip)
    rows = _sds((huffman.TABLE_SIZE // 128,), jnp.int32, one_chip)
    compiled = huffman.huffman_pack.lower(syms, table, meta, rows, n_words=1 << log2_words).compile()
    _pallas_text(compiled)
    sync, pieces = compiled.out_info
    assert sync.shape == (size // 1024,)
    assert sum(p.shape[0] for p in pieces) == 1 << log2_words


@pytest.mark.parametrize("tier", ["int8", "int4"])
def test_jitmode_roundtrip_compiles(one_chip, tier):
    policy = jitmode.JitPolicy(tier=tier)

    def roundtrip(x):
        return jitmode.decode(jitmode.encode(x, policy))

    x = _sds((4096, 1024), jnp.float32, one_chip)
    compiled = jax.jit(roundtrip).lower(x).compile()
    mem = compiled.memory_analysis()
    assert mem is None or mem.output_size_in_bytes == 4096 * 1024 * 4
    assert np.dtype(compiled.out_info.dtype) == np.float32


def test_compressed_dp_reduction_compiles(data_mesh):
    """The int8 error-feedback DP reduction compiles across the 2x2 mesh,
    with its collectives, on a gradient vector that fills no whole shard."""
    grads = {"w": jax.ShapeDtypeStruct((1000, 1001), jnp.float32),
             "b": jax.ShapeDtypeStruct((1001,), jnp.bfloat16)}
    dp = data_mesh.size
    fb = jax.eval_shape(functools.partial(gradc.init_feedback, dp=dp), grads)
    assert fb.shape[0] % (dp * gradc.BLOCK * gradc.ROW_GROUP) == 0

    def body(g, f):
        return gradc.compressed_reduce_tree(g, f, ("data",), "int8")

    region = jax.shard_map(body, mesh=data_mesh, in_specs=(P(), P("data")),
                           out_specs=(P(), P("data")), check_vma=False)
    rep, shard = NamedSharding(data_mesh, P()), NamedSharding(data_mesh, P("data"))
    g = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), grads)
    f = jax.ShapeDtypeStruct(fb.shape, fb.dtype, sharding=shard)
    text = jax.jit(region).lower(g, f).compile().as_text()
    assert "all-gather" in text
