"""Ahead-of-time compiles of the search path for a described TPU v5e.

Interpret mode (``tests/test_kernels.py``) checks what the kernels
compute; it accepts blocks and in-kernel shape casts that the TPU's
Mosaic compiler refuses. These tests hand the kernels, and one whole
``batch_search`` executable, to that compiler for a v5e chip that is
described, not attached: what it refuses here would raise on the first
hop on the chip. Nothing runs, so nothing here says anything about
results or times.

The topology is described inside a module-scoped fixture (never at
import): only one process at a time may load the TPU library, and the
fixture skips where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import hamming as hamming_k
from repro.kernels import l2dist as l2_k
from repro.kernels import page_scan as ps_k
from repro.kernels import pq_adc as adc_k

# (dim, page capacity, page degree, PQ subspaces): the HYBRID geometry
# ``PageANNConfig.resolve_capacity`` gives a 4 KB page at each dim
GEOMETRIES = [(32, 28, 48, 8), (128, 6, 48, 16), (768, 1, 48, 32)]
IO_BATCH = 5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _hlo(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
@pytest.mark.parametrize("adc", [True, False], ids=["adc", "members"])
@pytest.mark.parametrize("staged", [False, True], ids=["ids", "recs"])
@pytest.mark.parametrize("geom", GEOMETRIES, ids=lambda g: f"d{g[0]}")
def test_page_scan_compiles(one_chip, geom, staged, adc, masked):
    from repro.kernels.record_layout import record_rows

    d, cap, rp, m = geom
    rows = record_rows(cap, d, m)
    kw = dict(capacity=cap, dim=d, rp=rp, compute_adc=adc)
    shapes = [((IO_BATCH, rows, 128), jnp.float32)] if staged else [
        ((4096, rows, 128), jnp.float32), ((IO_BATCH,), jnp.int32),
    ]
    shapes += [((d,), jnp.float32), ((m, 256), jnp.float32)]
    if masked:
        shapes.append(((IO_BATCH, cap), jnp.float32))
    scan = ps_k.page_scan_recs if staged else ps_k.page_scan

    def fn(*a):
        if masked:
            *a, mask = a
            return scan(*a, member_mask=mask, **kw)
        return scan(*a, **kw)

    assert "tpu_custom_call" in _hlo(jax.jit(fn), *shapes, sharding=one_chip)


@pytest.mark.parametrize("m", [8, 16, 32])
def test_pq_adc_compiles(one_chip, m):
    fn = jax.jit(lambda c, lut: adc_k.pq_adc(c, lut))
    hlo = _hlo(fn, ((IO_BATCH * 48, m), jnp.uint8), ((m, 256), jnp.float32),
               sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("d", [32, 128, 768])
def test_l2dist_compiles(one_chip, d):
    fn = jax.jit(lambda q, x: l2_k.l2_distance(q, x))
    hlo = _hlo(fn, ((64, d), jnp.float32), ((4096, d), jnp.float32),
               sharding=one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("w", [1, 2, 4])
def test_hamming_compiles(one_chip, w):
    fn = jax.jit(lambda c, q: hamming_k.hamming(c, q))
    hlo = _hlo(fn, ((1024, w), jnp.uint32), ((w,), jnp.uint32),
               sharding=one_chip)
    assert "tpu_custom_call" in hlo


def test_batch_search_executable_compiles(one_chip, monkeypatch):
    """The whole vmapped while-loop search at d = 128, with the kernel
    dispatch steered to Pallas as it is on a TPU backend."""
    from repro.core import MemoryMode, PageANNConfig, PageANNIndex
    from repro.core import search as search_mod
    from repro.data.pipeline import clustered_vectors
    from repro.kernels import ops

    d = 128
    x = clustered_vectors(400, d, num_clusters=8, seed=0)
    cfg = PageANNConfig(
        dim=d, graph_degree=16, build_beam=24, pq_subspaces=16,
        lsh_sample=256, memory_mode=MemoryMode.HYBRID,
    )
    index = PageANNIndex.build(x, cfg)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    data = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), jnp.asarray(a).dtype, sharding=one_chip
        ),
        index.data,
    )
    q = jax.ShapeDtypeStruct((64, d), jnp.float32, sharding=one_chip)
    try:
        hlo = search_mod.batch_search.lower(
            q, data, index.default_params,
            capacity=index.store.capacity, mode=cfg.memory_mode.value,
        ).compile().as_text()
    finally:
        # the trace took the TPU branch: no later CPU call may reuse it
        jax.clear_caches()
    assert "tpu_custom_call" in hlo
