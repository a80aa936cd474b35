"""Payload builder + jit'd wrappers + executor spec for the megascan.

The megascan's input contract is the **block-aligned packed payload**
(``build_payload``): every shard's shard-sorted signature rows are
padded *independently* to TM-row block boundaries and concatenated, so
each TM block belongs to exactly one shard slot.  That alignment is
what buys bit-for-bit parity with a per-shard launch sequence: a slot's
output column only ever accumulates its own blocks, in its own block
order, through the same one-hot MXU dot — blocks of other shards (and
padding rows, which carry an out-of-range slot) contribute exact float
zeros, and ``x + 0.0`` is bitwise ``x`` for the strictly-positive
``exp`` sums the scan produces.  The per-shard reference path
(``MegascanSpec.run_shard``) therefore runs the *same* fused segment-sum
kernels (PR 2) on a single-shard payload with the same TM padding — one
launch per shard, bit-identical partials — which is also the
interpret-mode fallback when a deployment wants to disable grouping.

``MegascanSpec`` is the executor-facing handle: ``scan_fns()`` returns
per-query scan fns whose composite ``run_shared_scan`` closure carries
the spec, so ``ShardTaskExecutor.map_shards`` can route a whole shard
group as ONE launch (``run_group``) while emitting per-(query, shard)
results in exactly the layout the cross-host gather already consumes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, Optional, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp

from repro.data.store import plan_blocked_layout
from repro.kernels.asym import kernel as _ka
from repro.kernels.asym.ops import _prep_queries
from repro.kernels.common import interpret_mode, on_tpu, pad_rows
from repro.kernels.hamming import kernel as _kh
from repro.kernels.megascan import kernel as _km


def _lane_pad(n: int) -> int:
    return max(128, -(-int(n) // 128) * 128)


@dataclasses.dataclass(frozen=True)
class MegascanPayload:
    """Block-aligned packed multi-shard signature payload.

    ``sig_t`` is word-major ([W, n_rows], the layout the kernels
    stream); its row (column) axis is grouped per shard slot, each slot
    padded to a TM multiple (padding rows are zero signatures with slot
    ``slot_pad``, an out-of-range slot that reduces into nothing);
    ``slots`` maps every row to its shard slot; ``doc_idx`` maps every
    row back to the global doc id (-1 for padding); ``block_slot[j]``
    names the single slot TM-block ``j`` belongs to."""

    sig_t: jax.Array        # [W, n_rows] uint32, device-resident
    slots: jax.Array        # [1, n_rows] int32, device-resident
    doc_idx: np.ndarray     # [n_rows] int64, -1 on padding rows
    counts: np.ndarray      # [n_slots] int64 real rows per slot
    block_slot: np.ndarray  # [n_blocks] int32 block -> slot
    shard_ids: Tuple[int, ...]
    tm: int
    n_slots: int
    n_blocks: int
    n_rows: int

    @property
    def sig(self) -> jax.Array:
        """[n_rows, W] row-major view (the oracles' layout)."""
        return self.sig_t.T

    @property
    def slot_pad(self) -> int:
        """Lane-padded slot-axis width (also the padding rows' slot)."""
        return _lane_pad(self.n_slots)

    @property
    def nbytes_streamed(self) -> int:
        """HBM bytes the scan streams through VMEM per launch."""
        return int(self.sig_t.size * 4 + self.slots.size * 4)


def build_payload(segments: Sequence[Tuple[np.ndarray, np.ndarray]],
                  *, tm: int = 256,
                  shard_ids: Optional[Sequence[int]] = None,
                  ) -> MegascanPayload:
    """Pack per-shard ``(signatures [c_i, W] uint32, doc_ids [c_i])``
    segments into one block-aligned payload.  Empty shards get zero
    blocks (their slot simply never appears in ``block_slot``)."""
    if not segments:
        raise ValueError("megascan payload needs at least one shard")
    if tm <= 0 or tm & (tm - 1) != 0:
        raise ValueError(f"tm must be a positive power of two, got {tm}")
    w = int(segments[0][0].shape[1])
    counts = np.array([len(s[0]) for s in segments], np.int64)
    row_starts, blocks, n_rows = plan_blocked_layout(counts, tm)
    n_slots = len(segments)
    slot_pad = _lane_pad(n_slots)
    sig = np.zeros((n_rows, w), np.uint32)
    slots = np.full(n_rows, slot_pad, np.int32)
    doc_idx = np.full(n_rows, -1, np.int64)
    for i, (seg_sig, seg_docs) in enumerate(segments):
        c = int(counts[i])
        if c == 0:
            continue
        r = int(row_starts[i])
        sig[r:r + c] = np.asarray(seg_sig, np.uint32)
        slots[r:r + c] = i
        doc_idx[r:r + c] = np.asarray(seg_docs, np.int64)
    block_slot = np.repeat(np.arange(n_slots, dtype=np.int32),
                           blocks).astype(np.int32)
    if shard_ids is None:
        shard_ids = range(n_slots)
    return MegascanPayload(
        sig_t=jnp.asarray(np.ascontiguousarray(sig.T)),
        slots=jnp.asarray(slots.reshape(1, -1)),
        doc_idx=doc_idx, counts=counts, block_slot=block_slot,
        shard_ids=tuple(int(s) for s in shard_ids),
        tm=int(tm), n_slots=n_slots, n_blocks=int(block_slot.shape[0]),
        n_rows=int(n_rows))


def megascan_segment_sums(payload: MegascanPayload, queries: jax.Array,
                          planes: Optional[jax.Array], bits: int,
                          *, mode: str = "asym", tb: int = 8,
                          temperature: float = 1.0,
                          double_buffer: "bool | None" = None,
                          interpret: "bool | None" = None) -> np.ndarray:
    """One-launch per-(query, shard-slot) partial sums over the packed
    payload: [B, n_slots] float64.  ``mode="asym"`` takes [B, dim] real
    query vectors (any norm) + hyperplanes; ``mode="hamming"`` takes
    [B, W] packed query signatures (``planes`` ignored).

    ``double_buffer`` picks the data-movement schedule (None = the
    explicit DMA schedule on TPU, Mosaic's BlockSpec grid pipeline in
    interpret mode); both are bit-identical."""
    if interpret is None:
        interpret = interpret_mode()
    if double_buffer is None:
        double_buffer = on_tpu()
    s_pad = payload.slot_pad
    if mode == "asym":
        q, b, tb = _prep_queries(queries, tb)
        if payload.n_rows == 0:
            return np.zeros((b, payload.n_slots), np.float64)
        pl_ = jnp.asarray(planes, jnp.float32)
        if double_buffer:
            out = _km.asym_megascan_segsum_db_kernel(
                q, pl_, payload.sig_t, payload.slots, bits, s_pad,
                tb=tb, tm=payload.tm, interpret=interpret,
                temperature=temperature)
        else:
            out = _ka.asym_segment_sum_kernel(
                q, pl_, payload.sig_t, payload.slots, bits, s_pad,
                tb=tb, tm=payload.tm, interpret=interpret,
                temperature=temperature)
    elif mode == "hamming":
        qp = jnp.asarray(queries, jnp.uint32)
        b = qp.shape[0]
        tb = min(tb, max(1, b))
        if payload.n_rows == 0:
            return np.zeros((b, payload.n_slots), np.float64)
        qp = pad_rows(qp, tb)
        if double_buffer:
            out = _km.hamming_megascan_segsum_db_kernel(
                qp, payload.sig_t, payload.slots, bits, s_pad,
                tn=tb, tm=payload.tm, interpret=interpret,
                temperature=temperature)
        else:
            out = _kh.hamming_segment_similarity_kernel(
                qp, payload.sig_t, payload.slots, bits, s_pad,
                tn=tb, tm=payload.tm, interpret=interpret,
                temperature=temperature)
    else:
        raise ValueError(f"unknown megascan mode {mode!r}")
    return np.asarray(out[:b, :payload.n_slots], np.float64)


def megascan_topk(payload: MegascanPayload, queries: jax.Array,
                  planes: jax.Array, bits: int, k: int,
                  *, tb: int = 8, temperature: float = 1.0,
                  pad_lanes: "bool | None" = None,
                  double_buffer: "bool | None" = None,
                  interpret: "bool | None" = None,
                  ) -> "tuple[np.ndarray, np.ndarray]":
    """Ranked megascan (asym mode): per-(query, shard-slot) top-k doc
    ids + values in one launch.  Returns ([B, n_slots, k] int64 doc
    ids, [B, n_slots, k] float64 values); a slot with fewer than k docs
    pads with id -1 / value -inf.  The kernel emits only per-tile
    bitonic candidates (K lane-padded on TPU, PR 4's rule); the final
    per-slot reduction over <= blocks*K candidates happens here."""
    if interpret is None:
        interpret = interpret_mode()
    if double_buffer is None:
        double_buffer = on_tpu()
    if pad_lanes is None:
        pad_lanes = on_tpu()
    q, b, tb = _prep_queries(queries, tb)
    k = int(k)
    ids = np.full((b, payload.n_slots, k), -1, np.int64)
    vals = np.full((b, payload.n_slots, k), -np.inf, np.float64)
    if payload.n_rows == 0 or k == 0:
        return ids, vals
    kp = _lane_pad(k) if pad_lanes else k
    if kp > payload.tm:
        raise ValueError(
            f"k={k} (lane-padded {kp}) exceeds payload tile tm={payload.tm}")
    kernel = (_km.asym_megascan_topk_db_kernel if double_buffer
              else _km.asym_megascan_topk_kernel)
    cvals, cpos = kernel(
        q, jnp.asarray(planes, jnp.float32), payload.sig_t, payload.slots,
        bits, kp, payload.n_slots, tb=tb, tm=payload.tm,
        interpret=interpret, temperature=temperature)
    cvals = np.asarray(cvals[:b])          # [B, n_blocks*kp] float32
    cpos = np.asarray(cpos[:b])            # [B, n_blocks*kp] int32
    lane = np.arange(kp)
    for s in range(payload.n_slots):
        blocks_s = np.nonzero(payload.block_slot == s)[0]
        if blocks_s.size == 0:
            continue
        cols = (blocks_s[:, None] * kp + lane[None, :]).ravel()
        v = cvals[:, cols]
        p = cpos[:, cols]
        kk = min(k, v.shape[1])
        # stable argsort on -v == lax.top_k order (ties -> lowest
        # candidate index first), matching asym_exp_topk's final stage
        order = np.argsort(-v, axis=1, kind="stable")[:, :kk]
        tv = np.take_along_axis(v, order, axis=1)
        tp = np.take_along_axis(p, order, axis=1)
        real = np.isfinite(tv)
        ids[:, s, :kk] = np.where(real, payload.doc_idx[tp], -1)
        vals[:, s, :kk] = np.where(real, tv.astype(np.float64), -np.inf)
    return ids, vals


# ----------------------------------------------------------------------
# executor-facing spec
# ----------------------------------------------------------------------
class MegascanSpec:
    """A batch of query scans the executor may run as ONE launch per
    shard group.  ``scan_fns()`` yields the per-query fns
    ``run_shared_scan`` expects; the composite closure it builds carries
    this spec, and a megakernel-enabled ``ShardTaskExecutor`` routes the
    whole group through ``run_group`` (one Pallas launch) instead of one
    task per shard.  ``run_shard`` is the per-shard fused parity
    reference — the same PR-2 segment-sum kernels on a single-shard
    payload with identical TM padding, hence bit-for-bit equal partials
    (see the module docstring for why the packing guarantees it).

    Results per (query, shard): a python float (sum-mode) or a
    ``{"doc_ids": int64[k_s], "values": float64[k_s]}`` dict
    (ranked mode, ``k_s = min(k, shard doc count)``)."""

    def __init__(self, index, query_vecs, *, ranked_k: Optional[int] = None,
                 mode: Optional[str] = None, tb: int = 8, tm: int = 256,
                 temperature: Optional[float] = None,
                 double_buffer: "bool | None" = None,
                 pad_lanes: "bool | None" = None):
        if index.doc_sig is None:
            raise ValueError("megascan needs doc signatures "
                             "(build_index(keep_doc_vectors=True))")
        self.index = index
        vecs = np.atleast_2d(np.asarray(query_vecs, np.float32))
        self.n_queries = vecs.shape[0]
        self.mode = mode or ("asym" if index.lsh_mode == "asym"
                             else "hamming")
        if ranked_k is not None and self.mode != "asym":
            raise ValueError("ranked megascan requires asym mode")
        self.ranked_k = ranked_k
        self.tb = int(tb)
        self.tm = int(tm)
        self.temperature = float(index.temperature if temperature is None
                                 else temperature)
        self.double_buffer = double_buffer
        self.pad_lanes = pad_lanes
        dev = index._fused_device_arrays()
        self.planes = dev["planes"]
        if self.mode == "asym":
            self.queries = jnp.asarray(vecs, jnp.float32)
        else:
            from repro.core import lsh as lsh_mod
            self.queries = lsh_mod.pack_bits(lsh_mod.signature_bits(
                jnp.asarray(vecs, jnp.float32), self.planes))
        self.stats: Dict[str, int] = {"group_launches": 0,
                                      "shard_launches": 0}
        self.last_record: Optional[dict] = None

    # -- payloads ------------------------------------------------------
    def _payload(self, shard_ids: Tuple[int, ...]) -> MegascanPayload:
        return self.index.megascan_payload(shard_ids, tm=self.tm)

    # -- compute -------------------------------------------------------
    def _scan(self, payload: MegascanPayload):
        """Run the scan over one payload; returns the dense per-slot
        arrays (sum-mode [B, S] or ranked ([B, S, k], [B, S, k]))."""
        if self.ranked_k is None:
            return megascan_segment_sums(
                payload, self.queries, self.planes, self.index.bits,
                mode=self.mode, tb=self.tb, temperature=self.temperature,
                double_buffer=self.double_buffer)
        return megascan_topk(
            payload, self.queries, self.planes, self.index.bits,
            self.ranked_k, tb=self.tb, temperature=self.temperature,
            pad_lanes=self.pad_lanes, double_buffer=self.double_buffer)

    def _extract(self, payload: MegascanPayload, dense, slot: int,
                 qi: int):
        if self.ranked_k is None:
            return float(dense[qi, slot])
        ids, vals = dense
        k_s = int(min(self.ranked_k, payload.counts[slot]))
        return {"doc_ids": np.asarray(ids[qi, slot, :k_s]),
                "values": np.asarray(vals[qi, slot, :k_s])}

    def _flops(self, payload: MegascanPayload) -> int:
        b = self.n_queries
        bits = int(self.index.bits)
        rows = int(payload.n_rows)
        if self.mode == "asym":
            dim = int(self.planes.shape[1])
            proj = 2 * b * bits * dim
            score = 2 * b * rows * bits
        else:
            proj = 0
            score = 3 * b * rows * (bits // 32)
        reduce_ = 2 * b * rows * payload.slot_pad
        return proj + score + reduce_

    def run_group(self, shard_ids: Sequence[int],
                  queries_of: Dict[int, Iterable[int]]) -> dict:
        """ONE launch for the whole shard group; returns
        ``{shard_id: {query_index: result}}`` — exactly the layout the
        shared-scan gather consumes."""
        ids = tuple(int(s) for s in shard_ids)
        payload = self._payload(ids)
        t0 = time.perf_counter()
        dense = self._scan(payload)
        wall = time.perf_counter() - t0
        self.stats["group_launches"] += 1
        self.last_record = {
            "kind": "megascan", "mode": self.mode,
            "device_kind": jax.devices()[0].device_kind,
            "ranked": self.ranked_k is not None, "launches": 1,
            "shards": len(ids), "blocks": payload.n_blocks,
            "rows": payload.n_rows, "queries": self.n_queries,
            "tm": payload.tm, "prefetch_depth": 2,
            "double_buffer": bool(self.double_buffer
                                  if self.double_buffer is not None
                                  else on_tpu()),
            "bytes_streamed": payload.nbytes_streamed,
            "flops": self._flops(payload), "wall_s": wall,
        }
        out: Dict[int, dict] = {}
        for slot, sid in enumerate(ids):
            out[sid] = {qi: self._extract(payload, dense, slot, qi)
                        for qi in queries_of.get(sid, ())}
        return out

    def run_shard(self, shard_id: int, query_ids: Iterable[int]) -> dict:
        """Per-shard fused parity reference / fallback: one launch for
        THIS shard only, same kernels + padding as the group path."""
        payload = self._payload((int(shard_id),))
        dense = self._scan(payload)
        self.stats["shard_launches"] += 1
        return {qi: self._extract(payload, dense, 0, qi)
                for qi in query_ids}

    # -- shared-scan integration --------------------------------------
    def scan_fns(self):
        """Per-query scan fns for ``run_shared_scan``; each carries this
        spec so spec-aware layers can fuse the whole batch."""
        fns = []
        for qi in range(self.n_queries):
            def fn(shard, _qi=qi):
                return self.run_shard(shard.shard_id, (_qi,))[_qi]
            fn.megascan = self
            fn.query_index = qi
            fns.append(fn)
        return fns
