"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode accepts kernels that Mosaic, the TPU kernel compiler,
refuses (unaligned slices, shape casts, too much VMEM).  These tests
lower each kernel with ``interpret=False`` for one chip of a v5e
topology that is described, not attached, at the widths ``chip_smoke.py``
runs: PV-DBOW dim 100, 256-bit signatures (W = 8 words), 200k
documents, ~2k shard slots.  Nothing runs; a compile that passes is not
a chip run.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.asym import kernel as asym_k
from repro.kernels.hamming import kernel as ham_k
from repro.kernels.kmeans import kernel as km_k
from repro.kernels.megascan import kernel as mega_k
from repro.kernels.negsamp import kernel as ns_k

DIM, BITS, W = 100, 256, 8
N_DOCS, SLOTS, SLOT_PAD = 200_000, 1_950, 2_048
B = 32                          # one serving window of queries
TM, KP = 256, 128               # scoring tile, lane-padded top-k


def _pad(n, m):
    return -(-n // m) * m


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)``: an abstract argument on one v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_asym_similarity_compiles(shape):
    m = _pad(N_DOCS, TM)
    _compile(asym_k.asym_similarity_kernel,
             shape((B, DIM), jnp.float32), shape((BITS, DIM), jnp.float32),
             shape((W, m), jnp.uint32), bits=BITS, tm=TM, interpret=False,
             temperature=8.0)


def test_asym_segment_sum_compiles(shape):
    m = _pad(N_DOCS, TM)
    _compile(asym_k.asym_segment_sum_kernel,
             shape((B, DIM), jnp.float32), shape((BITS, DIM), jnp.float32),
             shape((W, m), jnp.uint32), shape((1, m), jnp.int32),
             bits=BITS, n_segments=SLOT_PAD, tm=TM, interpret=False,
             temperature=8.0)


def test_asym_topk_compiles(shape):
    m = _pad(N_DOCS, TM)
    _compile(asym_k.asym_topk_kernel,
             shape((B, DIM), jnp.float32), shape((BITS, DIM), jnp.float32),
             shape((W, m), jnp.uint32), bits=BITS, k=KP, m_total=N_DOCS,
             tm=TM, interpret=False, temperature=8.0)


def test_hamming_similarity_compiles(shape):
    m = _pad(N_DOCS, 512)
    _compile(ham_k.hamming_similarity_kernel,
             shape((B, W), jnp.uint32), shape((W, m), jnp.uint32),
             bits=BITS, tm=512, interpret=False, temperature=8.0)


def test_hamming_segment_sum_compiles(shape):
    m = _pad(N_DOCS, 512)
    _compile(ham_k.hamming_segment_similarity_kernel,
             shape((B, W), jnp.uint32), shape((W, m), jnp.uint32),
             shape((1, m), jnp.int32), bits=BITS, n_segments=SLOT_PAD,
             tm=512, interpret=False, temperature=8.0)


def _payload_rows():
    """Rows of a whole-fleet megascan payload: every slot padded to
    whole TM blocks (~103 docs per shard -> one block each, plus
    spill)."""
    return _pad(N_DOCS + SLOTS * TM // 2, TM)


def test_megascan_dma_segment_sum_compiles(shape):
    rows = _payload_rows()
    _compile(mega_k.asym_megascan_segsum_db_kernel,
             shape((B, DIM), jnp.float32), shape((BITS, DIM), jnp.float32),
             shape((W, rows), jnp.uint32), shape((1, rows), jnp.int32),
             bits=BITS, n_slots=SLOT_PAD, tm=TM, interpret=False,
             temperature=8.0)


@pytest.mark.parametrize("kernel", ["asym_megascan_topk_kernel",
                                    "asym_megascan_topk_db_kernel"])
def test_megascan_topk_compiles(shape, kernel):
    rows = _payload_rows()
    _compile(getattr(mega_k, kernel),
             shape((B, DIM), jnp.float32), shape((BITS, DIM), jnp.float32),
             shape((W, rows), jnp.uint32), shape((1, rows), jnp.int32),
             bits=BITS, k=KP, n_valid_slots=SLOTS, tm=TM, interpret=False,
             temperature=8.0)


def test_negsamp_grads_compile(shape):
    pairs, negatives = 8_192, 5
    _compile(ns_k.negsamp_grads_kernel,
             shape((pairs, DIM), jnp.float32),
             shape((pairs, DIM), jnp.float32),
             shape((pairs, negatives, DIM), jnp.float32),
             tb=256, interpret=False, temperature=8.0)


def test_kmeans_assign_compiles(shape):
    _compile(km_k.assign_kernel,
             shape((_pad(N_DOCS, 512), DIM), jnp.float32),
             shape((_pad(SLOTS, 8), DIM), jnp.float32),
             k_valid=SLOTS, tn=512, interpret=False)
