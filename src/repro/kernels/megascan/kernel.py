"""Pallas TPU megakernel: one-launch scan over a host's shard payloads.

The fused kernels (kernels/asym, kernels/hamming) reduce one contiguous
signature database per launch; at per-host shard counts in the hundreds
the per-shard launch cadence is dispatch-bound — kernel launch latency
and HBM<->VMEM round-trips dominate the very scan EmApprox is supposed
to make cheap.  This module restructures the per-host shared scan as a
*single* Pallas program over a packed multi-shard payload (see
``megascan.ops.build_payload``): every shard's signature rows are padded
to TM-block boundaries and concatenated, so each TM block belongs to
exactly one shard *slot* and the whole host group streams through VMEM
in one launch — the compile-once-scan-many idiom of levanter's
``Stacked`` scan-over-layers, applied to shard payloads instead of
transformer blocks.

Two data-movement schedules produce bit-identical results:

  * the *streamed* schedule (``megascan.ops`` routes it through the
    existing ``asym``/``hamming`` segment-sum kernels with shard-slot
    ids as the segment map) — Mosaic's BlockSpec grid pipeline already
    double-buffers the HBM->VMEM block copies;
  * the *double-buffered DMA* schedule here (``*_db_kernel``): the
    packed payload and its row -> slot map stay in HBM
    (``memory_space=ANY``) and the kernel itself prefetches block i+1
    into the alternate VMEM scratch slot with ``pltpu.make_async_copy``
    while the MXU scores block i — the explicit form of the same
    overlap, and the schedule that keeps working when the block
    sequence is the whole program (grid collapses to query tiles, so
    there is no M grid axis for Mosaic to pipeline).  The payload is
    word-major ([W, rows], see kernels/asym), so every block copy is a
    lane-aligned [W, TM] slice; nothing of payload size is ever
    resident in VMEM, which keeps a 2k-shard fleet inside the scoped
    VMEM budget.

Both accumulate per-(query, slot) partials block-by-block in a resident
[TB, S] VMEM output — identical op shapes and identical accumulation
order, hence bit-for-bit equality between the schedules *and* with a
per-shard launch sequence over the same blocks (a slot's column only
ever sums its own blocks, in the same order, with the same one-hot dot;
other blocks contribute exact float zeros).

Ranked epilogue (``kernels.blocks.topk_block``): instead of
``jax.lax.top_k`` (which Mosaic does not lower in a kernel), each tile
runs a *lane-padded bitonic sort* (``bitonic_sort_desc``) — descending
by value, ties broken by ascending index, exactly ``jax.lax.top_k``'s
order — and emits only its K best (value, payload-position)
candidates, so ranked queries never materialize full per-doc scores.
K is lane-padded to 128 multiples by the ops wrapper on TPU, and TM
must be a power of two.  The DMA schedule writes each block's
candidates back to HBM through a second 2-slot ring, so its VMEM use
does not grow with the payload either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.asym.kernel import exp_sim_tile
from repro.kernels.blocks import segsum_block, topk_block
from repro.kernels.hamming.kernel import sim_tile


# ----------------------------------------------------------------------
# double-buffered DMA schedule: grid = query tiles only; the kernel owns
# the block loop and prefetches block j+1 while scoring block j
# ----------------------------------------------------------------------
def _prefetch_loop(sig_hbm, slot_hbm, sbuf, lbuf, sems, *, n_blocks: int,
                   tm: int, visit) -> None:
    """Stream the payload's TM-column blocks (signatures [W, TM] and
    their slot row [1, TM]) through the 2-slot VMEM ring, calling
    ``visit(j, sig_block, slot_block)`` on block j while block j+1 is
    in flight."""
    def copies(slot, j):
        start = pl.multiple_of(j * tm, tm)
        return (
            pltpu.make_async_copy(sig_hbm.at[:, pl.ds(start, tm)],
                                  sbuf.at[slot], sems.at[0, slot]),
            pltpu.make_async_copy(slot_hbm.at[:, pl.ds(start, tm)],
                                  lbuf.at[slot], sems.at[1, slot]),
        )

    for c in copies(0, 0):
        c.start()

    def step(j, carry):
        cur = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_blocks)
        def _prefetch():
            for c in copies(1 - cur, j + 1):
                c.start()

        for c in copies(cur, j):
            c.wait()
        visit(j, sbuf[cur], lbuf[cur])
        return carry

    jax.lax.fori_loop(0, n_blocks, step, 0)


def _ring_scratch(w: int, tm: int) -> list:
    return [pltpu.VMEM((2, w, tm), jnp.uint32),
            pltpu.VMEM((2, 1, tm), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2))]


def _asym_segsum_db_body(q_ref, planes_ref, slot_hbm, sig_hbm, out_ref,
                         sbuf, lbuf, sems, *, bits: int, temperature: float,
                         n_blocks: int, tm: int):
    q = q_ref[...]
    planes = planes_ref[...]
    out_ref[...] = jnp.zeros_like(out_ref)

    def visit(j, sig, seg):
        segsum_block(exp_sim_tile(q, planes, sig, bits, temperature), seg,
                     out_ref)

    _prefetch_loop(sig_hbm, slot_hbm, sbuf, lbuf, sems, n_blocks=n_blocks,
                   tm=tm, visit=visit)


def _hamming_segsum_db_body(q_ref, slot_hbm, sig_hbm, out_ref, sbuf, lbuf,
                            sems, *, bits: float, temperature: float,
                            n_blocks: int, tm: int):
    q = q_ref[...]
    out_ref[...] = jnp.zeros_like(out_ref)

    def visit(j, sig, seg):
        segsum_block(sim_tile(q, sig, bits, temperature), seg, out_ref)

    _prefetch_loop(sig_hbm, slot_hbm, sbuf, lbuf, sems, n_blocks=n_blocks,
                   tm=tm, visit=visit)


@functools.partial(jax.jit, static_argnames=(
    "bits", "n_slots", "tb", "tm", "interpret", "temperature"))
def asym_megascan_segsum_db_kernel(
    q: jax.Array,            # [B, dim] float32, rows unit-normalized
    planes: jax.Array,       # [bits, dim] float32
    sig: jax.Array,          # [W, n_blocks*TM] uint32, block-aligned
    slot_ids: jax.Array,     # [1, n_blocks*TM] int32 row -> shard slot
    bits: int,
    n_slots: int,            # S (lane-padded by the ops wrapper)
    *,
    tb: int = 8,
    tm: int = 256,
    interpret: bool = False,
    temperature: float = 1.0,
) -> jax.Array:
    """[B, S] per-(query, shard-slot) partial sums, one launch for the
    whole packed payload; signature and slot blocks are DMA'd
    HBM->VMEM through a 2-slot scratch ring (prefetch block j+1 while
    scoring block j)."""
    b, dim = q.shape
    w, mp = sig.shape
    assert mp % tm == 0, (mp, tm)
    body = functools.partial(_asym_segsum_db_body, bits=int(bits),
                             temperature=float(temperature),
                             n_blocks=int(mp // tm), tm=int(tm))
    return pl.pallas_call(
        body,
        grid=(pl.cdiv(b, tb),),
        in_specs=[
            pl.BlockSpec((tb, dim), lambda i: (i, 0)),
            pl.BlockSpec((planes.shape[0], dim), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tb, n_slots), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_slots), jnp.float32),
        scratch_shapes=_ring_scratch(w, tm),
        interpret=interpret,
        name="asym_megascan_segsum_db_kernel",
    )(q, planes, slot_ids, sig)


@functools.partial(jax.jit, static_argnames=(
    "bits", "n_slots", "tn", "tm", "interpret", "temperature"))
def hamming_megascan_segsum_db_kernel(
    q_packed: jax.Array,     # [N, W] uint32
    sig: jax.Array,          # [W, n_blocks*TM] uint32, block-aligned
    slot_ids: jax.Array,     # [1, n_blocks*TM] int32
    bits: int,
    n_slots: int,
    *,
    tn: int = 8,
    tm: int = 256,
    interpret: bool = False,
    temperature: float = 1.0,
) -> jax.Array:
    n, w = q_packed.shape
    w2, mp = sig.shape
    assert w == w2 and mp % tm == 0, (w, w2, mp, tm)
    body = functools.partial(_hamming_segsum_db_body, bits=float(bits),
                             temperature=float(temperature),
                             n_blocks=int(mp // tm), tm=int(tm))
    return pl.pallas_call(
        body,
        grid=(pl.cdiv(n, tn),),
        in_specs=[
            pl.BlockSpec((tn, w), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tn, n_slots), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_slots), jnp.float32),
        scratch_shapes=_ring_scratch(w, tm),
        interpret=interpret,
        name="hamming_megascan_segsum_db_kernel",
    )(q_packed, slot_ids, sig)


# ----------------------------------------------------------------------
# ranked mode: per-tile bitonic top-k candidates
# ----------------------------------------------------------------------
def _asym_topk_stream_body(q_ref, planes_ref, db_ref, slot_ref, vals_ref,
                           idx_ref, *, bits: int, temperature: float,
                           k: int, tm: int, n_valid_slots: int):
    j = pl.program_id(1)
    tile = exp_sim_tile(q_ref[...], planes_ref[...], db_ref[...], bits,
                        temperature)
    vals, pos = topk_block(tile, slot_ref[...] < n_valid_slots, j * tm, k=k)
    vals_ref[...] = vals
    idx_ref[...] = pos


def _asym_topk_db_body(q_ref, planes_ref, slot_hbm, sig_hbm, vals_hbm,
                       idx_hbm, sbuf, lbuf, sems, vbuf, ibuf, osems, *,
                       bits: int, temperature: float, k: int, tm: int,
                       tb: int, n_blocks: int, n_valid_slots: int):
    q = q_ref[...]
    planes = planes_ref[...]
    row = pl.multiple_of(pl.program_id(0) * tb, tb)

    def out_copies(slot, j):
        col = pl.multiple_of(j * k, k)
        return (
            pltpu.make_async_copy(vbuf.at[slot],
                                  vals_hbm.at[pl.ds(row, tb), pl.ds(col, k)],
                                  osems.at[0, slot]),
            pltpu.make_async_copy(ibuf.at[slot],
                                  idx_hbm.at[pl.ds(row, tb), pl.ds(col, k)],
                                  osems.at[1, slot]),
        )

    def visit(j, sig, seg):
        slot = jax.lax.rem(j, 2)

        @pl.when(j >= 2)
        def _reuse():       # block j-2's write-back left this slot
            for c in out_copies(slot, j - 2):
                c.wait()

        tile = exp_sim_tile(q, planes, sig, bits, temperature)
        vals, pos = topk_block(tile, seg < n_valid_slots, j * tm, k=k)
        vbuf[slot] = vals
        ibuf[slot] = pos
        for c in out_copies(slot, j):
            c.start()

    _prefetch_loop(sig_hbm, slot_hbm, sbuf, lbuf, sems, n_blocks=n_blocks,
                   tm=tm, visit=visit)
    for j in range(max(0, n_blocks - 2), n_blocks):
        for c in out_copies(j % 2, j):
            c.wait()


@functools.partial(jax.jit, static_argnames=(
    "bits", "k", "n_valid_slots", "tb", "tm", "interpret", "temperature"))
def asym_megascan_topk_kernel(
    q: jax.Array,            # [B, dim] float32, rows unit-normalized
    planes: jax.Array,       # [bits, dim] float32
    sig: jax.Array,          # [W, n_blocks*TM] uint32, block-aligned
    slot_ids: jax.Array,     # [1, n_blocks*TM] int32
    bits: int,
    k: int,
    n_valid_slots: int,      # real (unpadded) slot count, for masking
    *,
    tb: int = 8,
    tm: int = 256,
    interpret: bool = False,
    temperature: float = 1.0,
) -> "tuple[jax.Array, jax.Array]":
    """Streamed-schedule ranked megascan: ([B, n_blocks*K] values,
    [B, n_blocks*K] int32 payload positions) — per-tile bitonic top-k
    candidates only; the ops wrapper groups candidates by shard slot
    and runs the cheap final per-slot top-k."""
    b, dim = q.shape
    w, mp = sig.shape
    assert mp % tm == 0 and k <= tm, (mp, tm, k)
    n_blocks = mp // tm
    body = functools.partial(_asym_topk_stream_body, bits=int(bits),
                             temperature=float(temperature), k=int(k),
                             tm=int(tm), n_valid_slots=int(n_valid_slots))
    return pl.pallas_call(
        body,
        grid=(pl.cdiv(b, tb), n_blocks),
        in_specs=[
            pl.BlockSpec((tb, dim), lambda i, j: (i, 0)),
            pl.BlockSpec((planes.shape[0], dim), lambda i, j: (0, 0)),
            pl.BlockSpec((w, tm), lambda i, j: (0, j)),
            pl.BlockSpec((1, tm), lambda i, j: (0, j)),
        ],
        out_specs=(
            pl.BlockSpec((tb, k), lambda i, j: (i, j)),
            pl.BlockSpec((tb, k), lambda i, j: (i, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, n_blocks * k), jnp.float32),
            jax.ShapeDtypeStruct((b, n_blocks * k), jnp.int32),
        ),
        interpret=interpret,
        name="asym_megascan_topk_kernel",
    )(q, planes, sig, slot_ids)


@functools.partial(jax.jit, static_argnames=(
    "bits", "k", "n_valid_slots", "tb", "tm", "interpret", "temperature"))
def asym_megascan_topk_db_kernel(
    q: jax.Array,
    planes: jax.Array,
    sig: jax.Array,
    slot_ids: jax.Array,
    bits: int,
    k: int,
    n_valid_slots: int,
    *,
    tb: int = 8,
    tm: int = 256,
    interpret: bool = False,
    temperature: float = 1.0,
) -> "tuple[jax.Array, jax.Array]":
    """Double-buffered DMA schedule of ``asym_megascan_topk_kernel`` —
    same per-block candidates; signature blocks are prefetched through
    the 2-slot input ring while the current block is scored, and each
    block's [TB, K] candidates leave through a 2-slot output ring."""
    b, dim = q.shape
    w, mp = sig.shape
    assert mp % tm == 0 and k <= tm, (mp, tm, k)
    n_blocks = mp // tm
    body = functools.partial(_asym_topk_db_body, bits=int(bits),
                             temperature=float(temperature), k=int(k),
                             tm=int(tm), tb=int(tb), n_blocks=int(n_blocks),
                             n_valid_slots=int(n_valid_slots))
    return pl.pallas_call(
        body,
        grid=(pl.cdiv(b, tb),),
        in_specs=[
            pl.BlockSpec((tb, dim), lambda i: (i, 0)),
            pl.BlockSpec((planes.shape[0], dim), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, n_blocks * k), jnp.float32),
            jax.ShapeDtypeStruct((b, n_blocks * k), jnp.int32),
        ),
        scratch_shapes=_ring_scratch(w, tm) + [
            pltpu.VMEM((2, tb, k), jnp.float32),
            pltpu.VMEM((2, tb, k), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=interpret,
        name="asym_megascan_topk_db_kernel",
    )(q, planes, slot_ids, sig)
