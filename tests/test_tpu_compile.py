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
BAND = 256                      # slots a shard-sorted doc tile touches


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


def _compile(shape, name):
    fn, args, static = _cases(shape)[name]
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_asym_similarity_compiles(shape):
    _compile(shape, "asym_similarity_kernel")


def test_asym_segment_sum_compiles(shape):
    _compile(shape, "asym_segment_sum_kernel")


def test_asym_segment_sum_band_compiles(shape):
    """The planner's banded segment sum (a 256-slot window per doc
    tile, its start by scalar prefetch, a lane-dim slice of the
    resident output) compiles, under the full-width kernel's name: the
    trace finds the planner's kernel by it."""
    fn, args, static = _cases(shape)["asym_segment_sum_band_kernel"]
    lowered = fn.lower(*args, **static)
    assert 'kernel_name = "asym_segment_sum_kernel"' in lowered.as_text()
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_asym_topk_compiles(shape):
    _compile(shape, "asym_topk_kernel")


def test_hamming_similarity_compiles(shape):
    _compile(shape, "hamming_similarity_kernel")


def test_hamming_segment_sum_compiles(shape):
    _compile(shape, "hamming_segment_similarity_kernel")


def _payload_rows():
    """Rows of a whole-fleet megascan payload: every slot padded to
    whole TM blocks (~103 docs per shard -> one block each, plus
    spill)."""
    return _pad(N_DOCS + SLOTS * TM // 2, TM)


def test_megascan_dma_segment_sum_compiles(shape):
    _compile(shape, "asym_megascan_segsum_db_kernel")


@pytest.mark.parametrize("kernel", ["asym_megascan_topk_kernel",
                                    "asym_megascan_topk_db_kernel"])
def test_megascan_topk_compiles(shape, kernel):
    _compile(shape, kernel)


def test_negsamp_grads_compile(shape):
    _compile(shape, "negsamp_grads_kernel")


def test_kmeans_assign_compiles(shape):
    _compile(shape, "kmeans_assign_kernel")


def _cases(shape):
    """Pallas kernel name -> (jitted entry point, abstract args, static
    args), at the widths above."""
    m, mh, rows = _pad(N_DOCS, TM), _pad(N_DOCS, 512), _payload_rows()
    q, planes = shape((B, DIM), jnp.float32), shape((BITS, DIM), jnp.float32)
    qsig = shape((B, W), jnp.uint32)
    db, dbh = shape((W, m), jnp.uint32), shape((W, mh), jnp.uint32)
    seg, segh = shape((1, m), jnp.int32), shape((1, mh), jnp.int32)
    pay, slot = shape((W, rows), jnp.uint32), shape((1, rows), jnp.int32)
    common = dict(interpret=False, temperature=8.0)
    topk = dict(bits=BITS, k=KP, n_valid_slots=SLOTS, tm=TM, **common)
    return {
        "asym_similarity_kernel": (asym_k.asym_similarity_kernel,
                                   (q, planes, db),
                                   dict(bits=BITS, tm=TM, **common)),
        "asym_segment_sum_kernel": (asym_k.asym_segment_sum_kernel,
                                    (q, planes, db, seg),
                                    dict(bits=BITS, n_segments=SLOT_PAD,
                                         tm=TM, **common)),
        "asym_segment_sum_band_kernel": (
            asym_k.asym_segment_sum_kernel, (q, planes, db, seg),
            dict(bits=BITS, n_segments=SLOT_PAD, tm=TM,
                 starts=shape((m // TM,), jnp.int32), width=BAND,
                 **common)),
        "asym_topk_kernel": (asym_k.asym_topk_kernel, (q, planes, db),
                             dict(bits=BITS, k=KP, m_total=N_DOCS, tm=TM,
                                  **common)),
        "hamming_distance_kernel": (ham_k.hamming_distance_kernel,
                                    (qsig, dbh),
                                    dict(tm=512, interpret=False)),
        "hamming_similarity_kernel": (ham_k.hamming_similarity_kernel,
                                      (qsig, dbh),
                                      dict(bits=BITS, tm=512, **common)),
        "hamming_segment_similarity_kernel": (
            ham_k.hamming_segment_similarity_kernel, (qsig, dbh, segh),
            dict(bits=BITS, n_segments=SLOT_PAD, tm=512, **common)),
        "asym_megascan_segsum_db_kernel": (
            mega_k.asym_megascan_segsum_db_kernel, (q, planes, pay, slot),
            dict(bits=BITS, n_slots=SLOT_PAD, tm=TM, **common)),
        "hamming_megascan_segsum_db_kernel": (
            mega_k.hamming_megascan_segsum_db_kernel, (qsig, pay, slot),
            dict(bits=BITS, n_slots=SLOT_PAD, tm=TM, **common)),
        "asym_megascan_topk_kernel": (mega_k.asym_megascan_topk_kernel,
                                      (q, planes, pay, slot), topk),
        "asym_megascan_topk_db_kernel": (
            mega_k.asym_megascan_topk_db_kernel, (q, planes, pay, slot),
            topk),
        "negsamp_grads_kernel": (
            ns_k.negsamp_grads_kernel,
            (shape((8_192, DIM), jnp.float32),
             shape((8_192, DIM), jnp.float32),
             shape((8_192, 5, DIM), jnp.float32)),
            dict(tb=256, **common)),
        "kmeans_assign_kernel": (
            km_k.assign_kernel,
            (shape((_pad(N_DOCS, 512), DIM), jnp.float32),
             shape((_pad(SLOTS, 8), DIM), jnp.float32)),
            dict(k_valid=SLOTS, tn=512, interpret=False)),
    }


KERNEL_NAMES = ["asym_similarity_kernel", "asym_segment_sum_kernel",
                "asym_topk_kernel", "hamming_distance_kernel",
                "hamming_similarity_kernel",
                "hamming_segment_similarity_kernel",
                "asym_megascan_segsum_db_kernel",
                "hamming_megascan_segsum_db_kernel",
                "asym_megascan_topk_kernel", "asym_megascan_topk_db_kernel",
                "negsamp_grads_kernel", "kmeans_assign_kernel"]


@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_lowered_kernel_carries_its_name(shape, name):
    """Every ``pallas_call`` passes a stable ``name=``: the TPU custom
    call (and so the op in a device trace) is named after it, whatever
    jitted function wraps the kernel."""
    fn, args, static = _cases(shape)[name]
    text = fn.lower(*args, **static).as_text()
    assert f'kernel_name = "{name}"' in text
