"""The approximation index (paper Fig. 1 / Fig. 2 steps p1-p2).

Contents:
  * word vectors           [V, dim]        (PV-DBOW)
  * shard vectors          [n_shards, dim] (mean of member doc vectors)
  * optional doc vectors   [n_docs, dim]   (needed for recsys + allocation)
  * LSH packed signatures for words and shards + the shared hyperplanes
  * document-frequency table for BM25 scoring (ranked retrieval)

Query-time API (paper Fig. 2 step a1): compose a query vector from word
vectors, score it against shard signatures (XOR+popcount Hamming ->
exp-cosine), normalize into sampling probabilities.

Batched scoring (the serving hot path): ``shard_similarities_batch``
and ``_exp_sim_batch`` score a [B, dim] block of query vectors against
every target signature in one pass — the asym path becomes a single
[M, bits] @ [bits, B] GEMM instead of B GEMVs, the sym path packs all
B query signatures once and rides the Hamming kernel's multi-query
``tn`` tiles, and the asym+kernel path runs the fused Pallas kernel in
``kernels/asym`` (projection + sign-matmul + exp-cosine in VMEM).
Single-query scoring stays on the latency-tuned numpy path; batched
scoring trades a little latency for throughput and is what
``core/queries/batch.QueryBatch`` uses.

Fused reductions: for doc-granular scoring the planner only consumes
per-shard sums (M = n_docs >> n_shards), so ``shard_similarities_batch
(..., fused=True)`` routes kernel-backed indices through the fused
segment-sum kernels — doc signatures are fed shard-sorted and each tile
reduces into a narrow band of shard slots in VMEM, so the [B, n_docs]
intermediate never reaches HBM.  ``topk_doc_similarities_batch`` is the
ranked analogue (fused in-kernel top-k).  The unfused ``_exp_sim_batch``
+ ``_sum_docs_to_shards_batch`` route is kept as the parity reference
(and as the non-kernel hot path, vectorized via one shard-sorted
``np.add.reduceat``).

The index is deliberately tiny relative to the corpus (paper Table II:
125 MB for 62 GB) — LSH compresses each 100-dim fp32 vector 64x.  Here
the exact compression is dim*4*8/bits bits per item.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import lsh as lsh_mod
from repro.core import pv_dbow as pv
from repro.core.sampling import similarity_probabilities
from repro.data.store import ShardedCorpus, atomic_savez
from repro.kernels.common import WordMajorSigs, word_major
from repro.runtime.generation import GenerationClock
from repro.utils import spans


@dataclasses.dataclass
class ApproxIndex:
    word_vecs: np.ndarray          # [V, dim] float32 (unit rows)
    shard_vecs: np.ndarray         # [n_shards, dim] float32
    doc_vecs: Optional[np.ndarray]  # [n_docs, dim] or None
    planes: np.ndarray             # [bits, dim] LSH hyperplanes
    word_sig: np.ndarray           # [V, bits//32] uint32
    shard_sig: np.ndarray          # [n_shards, bits//32] uint32
    doc_sig: Optional[np.ndarray]  # [n_docs, bits//32] uint32 or None
    bits: int
    doc_freq: np.ndarray           # [V] int64 document frequency (BM25)
    n_docs: int
    avg_doc_len: float
    use_lsh: bool = True           # False = score with real-valued vectors
    use_kernel: bool = False       # route Hamming through the Pallas kernel
    # "sym": paper-faithful two-sided Hamming (exp(beta cos(pi m/L)));
    # "asym": beyond-paper asymmetric scoring — stored side quantized,
    # query side real — same index bytes, ~half the quantization noise.
    lsh_mode: str = "asym"
    # "shard": paper Eq 10 (one vector per shard);  "doc": beyond-paper
    # doc-granular scoring (see shard_similarities).
    granularity: str = "shard"
    _doc_shard_ids: Optional[np.ndarray] = None  # doc_id -> shard_id
    # Scoring temperature: similarities are exp(beta * cos).  Must match
    # the temperature the PV-DBOW model was trained with so that
    # exp(beta cos) ~ exp(PMI - log k) ~ p(q|d) (paper Eq 5); see
    # PVDBOWConfig.temperature.
    temperature: float = 1.0
    # The joint word/doc mean subtracted by build_index(center=True) —
    # persisted so live ingest can put incrementally inferred doc
    # vectors through the identical centering transform (None for
    # uncentered indexes and pre-PR-10 saves).
    center_mean: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    # content generation
    # ------------------------------------------------------------------
    @property
    def clock(self) -> GenerationClock:
        """The generation authority this index bumps its *content* axis
        through.  Lazily a private clock so a standalone index works
        un-wired; ``build_serving_stack`` rebinds it to the stack's
        shared clock via ``use_clock`` so the cache/executor fence on
        the same handle.  Kept off the dataclass fields: it is identity
        state, not index content — ``dataclasses.replace`` (the ingest
        refresh) and save/load must not carry it."""
        c = getattr(self, "_gen_clock", None)
        if c is None:
            c = GenerationClock()
            object.__setattr__(self, "_gen_clock", c)
        return c

    def use_clock(self, clock: GenerationClock) -> "ApproxIndex":
        """Bind this index to a shared ``GenerationClock``; returns self."""
        object.__setattr__(self, "_gen_clock", clock)
        return self

    # ------------------------------------------------------------------
    # query-time scoring
    # ------------------------------------------------------------------
    def query_vector(self, word_ids: Sequence[int]) -> np.ndarray:
        """q = sum of word vectors (paper Sec. III)."""
        q = self.word_vecs[np.asarray(list(word_ids), np.int64)].sum(axis=0)
        return q

    def _signs_cache(self, target_sig: np.ndarray, role: str) -> np.ndarray:
        """Unpacked ±1 sign matrix for asym scoring, cached per target
        set.  Pure numpy keeps single-query latency at ~100 us; routing
        tiny index lookups through jax device dispatch costs ~3-50 ms
        per query (measured), swamping the similarity math itself.

        ``role`` ("shard" | "doc" | "word") is the cache key: keying on
        ``id(target_sig)`` — the old scheme — is unsound because ids are
        reused after garbage collection, so a stale entry could be
        served for a different signature array."""
        cache = getattr(self, "_signs", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_signs", cache)
        if role not in cache:
            bits = np.unpackbits(
                target_sig.view(np.uint8), bitorder="little",
            ).reshape(target_sig.shape[0], -1)[:, : self.bits]
            cache[role] = (2.0 * bits - 1.0).astype(np.float32)
        return cache[role]

    def _kernel_sigs(self, target_sig: np.ndarray,
                     role: str) -> "WordMajorSigs":
        """Device-resident word-major signatures (the kernels' layout,
        ``kernels/common.WordMajorSigs``) for the kernel routes, made and
        uploaded once per target set; keyed by ``role`` as in
        ``_signs_cache``."""
        cache = getattr(self, "_kernel_sig", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_kernel_sig", cache)
        if role not in cache:
            cache[role] = word_major(target_sig)
        return cache[role]

    def _exp_sim(self, vec: np.ndarray, target_sig: np.ndarray,
                 target_vecs: np.ndarray, role: str) -> np.ndarray:
        """exp(beta * cos) similarity of one vector against a signed set."""
        if self.use_lsh and self.lsh_mode == "asym":
            if self.use_kernel:
                cos = lsh_mod.asymmetric_cosine(
                    jnp.asarray(vec, jnp.float32), jnp.asarray(target_sig),
                    jnp.asarray(self.planes), self.bits)
                cos = np.asarray(cos, np.float64)
            else:
                q = np.asarray(vec, np.float64)
                q = q / max(np.linalg.norm(q), 1e-9)
                proj = (self.planes.astype(np.float64) @ q).astype(np.float32)
                signs = self._signs_cache(target_sig, role)
                scale = 1.0 / (self.bits * np.sqrt(2.0 / np.pi))
                cos = np.clip(signs @ proj * scale, -1.0, 1.0).astype(np.float64)
            return np.exp(self.temperature * cos)
        if self.use_lsh:
            q = np.asarray(vec, np.float32)
            qsig = lsh_mod.pack_bits(
                lsh_mod.signature_bits(jnp.asarray(q[None, :]), jnp.asarray(self.planes))
            )
            if self.use_kernel:
                from repro.kernels.hamming import ops as hamming_ops
                sims = hamming_ops.hamming_similarity(
                    qsig, self._kernel_sigs(target_sig, role), self.bits,
                    temperature=self.temperature)
            else:
                sims = lsh_mod.hamming_similarity(
                    qsig, jnp.asarray(target_sig), self.bits,
                    temperature=self.temperature)
            return np.asarray(sims[0], np.float64)
        # real-valued path: exp(beta cos) with unit-normalized query
        q = np.asarray(vec, np.float64)
        qn = q / max(np.linalg.norm(q), 1e-9)
        return np.exp(self.temperature * (target_vecs.astype(np.float64) @ qn))

    def _exp_sim_batch(self, vecs: np.ndarray, target_sig: np.ndarray,
                       target_vecs: np.ndarray, role: str) -> np.ndarray:
        """exp(beta * cos) of a [B, dim] query block against a signed
        set; returns [B, M] float64.

        Matches ``_exp_sim`` row-for-row (same projection dtype walk)
        but runs every query in one pass: the asym path is a single
        [M, bits] @ [bits, B] GEMM, the sym path packs B signatures at
        once and scores through the multi-query Hamming tiles, and the
        asym+kernel path uses the fused Pallas kernel in kernels/asym.
        """
        vecs = np.atleast_2d(np.asarray(vecs))
        if self.use_lsh and self.lsh_mode == "asym":
            if self.use_kernel:
                from repro.kernels.asym import ops as asym_ops
                sims = asym_ops.asym_exp_similarity(
                    jnp.asarray(vecs, jnp.float32),
                    self._kernel_sigs(target_sig, role),
                    jnp.asarray(self.planes), self.bits,
                    temperature=self.temperature)
                with spans.span("emapprox.plan.wait"):
                    return np.asarray(sims, np.float64)
            q = np.asarray(vecs, np.float64)
            q = q / np.maximum(
                np.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
            proj = (self.planes.astype(np.float64) @ q.T).astype(np.float32)
            signs = self._signs_cache(target_sig, role)      # [M, bits]
            scale = 1.0 / (self.bits * np.sqrt(2.0 / np.pi))
            cos = np.clip(signs @ proj * scale, -1.0, 1.0)   # [M, B]
            return np.exp(self.temperature * cos.astype(np.float64)).T
        if self.use_lsh:
            qsig = lsh_mod.pack_bits(lsh_mod.signature_bits(
                jnp.asarray(vecs, jnp.float32), jnp.asarray(self.planes)))
            if self.use_kernel:
                from repro.kernels.hamming import ops as hamming_ops
                sims = hamming_ops.hamming_similarity(
                    qsig, self._kernel_sigs(target_sig, role), self.bits,
                    temperature=self.temperature)
            else:
                sims = lsh_mod.hamming_similarity(
                    qsig, jnp.asarray(target_sig), self.bits,
                    temperature=self.temperature)
            with spans.span("emapprox.plan.wait"):
                return np.asarray(sims, np.float64)
        q = np.asarray(vecs, np.float64)
        q = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
        return np.exp(self.temperature * (q @ target_vecs.astype(np.float64).T))

    def shard_similarities(self, query_word_ids: Sequence[int]) -> np.ndarray:
        """Similarity of the query to every shard.

        ``granularity='shard'`` is the paper's Eq 10: exp(q . s_bar) with
        s_bar the mean doc vector — a geometric mean of per-doc
        probabilities.  ``granularity='doc'`` (beyond-paper) sums
        exp(beta cos(q, d)) over member documents — the arithmetic mean,
        which is exactly proportional to the expected count
        sum_d |d| p(q|d) the pps sampler wants; it reuses the doc
        signatures already stored for recommendation, so index bytes are
        unchanged."""
        if self.granularity == "doc" and (self.doc_sig is not None or
                                          self.doc_vecs is not None):
            doc_sims = self._exp_sim(self.query_vector(query_word_ids),
                                     self.doc_sig, self.doc_vecs, "doc")
            return self._sum_docs_to_shards(doc_sims)
        return self._exp_sim(self.query_vector(query_word_ids),
                             self.shard_sig, self.shard_vecs, "shard")

    def query_vectors(self, queries: Sequence[Sequence[int]]) -> np.ndarray:
        """[B, dim] stack of query vectors (sum of word vectors each)."""
        return np.stack([self.query_vector(q) for q in queries])

    def query_signatures(self, vecs: np.ndarray) -> np.ndarray:
        """[B, bits//32] packed LSH signatures for query vectors under
        the index's own hyperplanes, on the pure-numpy path (no device
        dispatch) — the key material for the semantic query cache
        (``runtime/qcache``).  Bit-identical to the jax signing the
        index itself was built with."""
        return lsh_mod.sign_vectors_np(vecs, self.planes)

    def shard_similarities_batch(
            self, queries: Sequence[Sequence[int]], *,
            fused: bool = True) -> np.ndarray:
        """[B, n_shards] similarity of every query to every shard in one
        scoring pass — the batch analogue of ``shard_similarities`` (see
        ``_exp_sim_batch`` for how each LSH mode batches).

        ``fused=True`` (default) routes kernel-backed doc-granular
        scoring through the fused segment-sum kernels, which reduce the
        [B, n_docs] similarity tile directly into [B, n_shards] in VMEM
        — the doc-wide intermediate never reaches HBM.  ``fused=False``
        keeps the unfused ``_exp_sim_batch`` + numpy reduce route (the
        parity reference the fused tests pin against)."""
        return self._shard_sims_from_vectors(self.query_vectors(queries),
                                             fused=fused)

    def _shard_sims_from_vectors(self, vecs: np.ndarray, *,
                                 fused: bool = True) -> np.ndarray:
        doc_granular = self.granularity == "doc" and (
            self.doc_sig is not None or self.doc_vecs is not None)
        if not doc_granular:
            return self._exp_sim_batch(vecs, self.shard_sig,
                                       self.shard_vecs, "shard")
        if (fused and self.use_lsh and self.use_kernel
                and self.doc_sig is not None
                and self._doc_shard_ids is not None):
            return self._fused_doc_shard_sims_batch(vecs)
        doc_sims = self._exp_sim_batch(vecs, self.doc_sig,
                                       self.doc_vecs, "doc")
        return self._sum_docs_to_shards_batch(doc_sims)

    def _fused_device_arrays(self) -> dict:
        """Device-resident operands for the fused segment-sum kernels,
        uploaded once in the kernels' word-major layout and cached:
        re-uploading (or re-laying-out) the [n_docs, W] doc signature
        database per batch would push the whole index through HBM every
        ~2 ms serving window — traffic that dwarfs the [B, n_docs]
        intermediate the fusion saves."""
        dev = getattr(self, "_fused_dev", None)
        if dev is None:
            dev = dict(planes=jnp.asarray(self.planes, jnp.float32))
            if self._doc_shard_ids is not None:
                _, _, _, seg_sorted, sig_sorted = self._shard_sorted_docs()
                dev["seg"] = jnp.asarray(seg_sorted)
                dev["sig"] = word_major(sig_sorted)
            object.__setattr__(self, "_fused_dev", dev)
        return dev

    def _fused_doc_shard_sims_batch(self, vecs: np.ndarray) -> np.ndarray:
        """[B, n_shards] via the fused in-kernel segment reduction: doc
        signatures are fed shard-sorted so each kernel tile reduces into
        a narrow band of shard slots (kernels/asym, kernels/hamming)."""
        vecs = np.atleast_2d(np.asarray(vecs))
        dev = self._fused_device_arrays()
        n_shards = self.shard_vecs.shape[0]
        if self.lsh_mode == "asym":
            from repro.kernels.asym import ops as asym_ops
            out = asym_ops.asym_exp_segment_sum(
                jnp.asarray(vecs, jnp.float32), dev["sig"], dev["planes"],
                self.bits, dev["seg"], n_shards,
                temperature=self.temperature)
        else:
            from repro.kernels.hamming import ops as hamming_ops
            qsig = lsh_mod.pack_bits(lsh_mod.signature_bits(
                jnp.asarray(vecs, jnp.float32), dev["planes"]))
            out = hamming_ops.hamming_segment_similarity(
                qsig, dev["sig"], self.bits, dev["seg"], n_shards,
                temperature=self.temperature)
        # the blocking copy is the planner's wait for the device
        with spans.span("emapprox.plan.wait"):
            return np.asarray(out, np.float64)

    def topk_doc_similarities_batch(
            self, queries: Sequence[Sequence[int]], k: int = 10, *,
            fused: bool = True) -> "tuple[np.ndarray, np.ndarray]":
        """Ranked retrieval over *documents*: ([B, k] doc indices,
        [B, k] exp-similarities), rows sorted descending.

        With ``fused=True`` on a kernel-backed asym index the top-k
        reduction runs inside the Pallas kernel (per-tile candidates
        only leave VMEM); otherwise the [B, n_docs] matrix is scored
        unfused and reduced with an argsort — the parity reference."""
        if self.doc_sig is None and self.doc_vecs is None:
            raise ValueError("index was built without document vectors")
        vecs = np.atleast_2d(self.query_vectors(queries))
        if (fused and self.use_lsh and self.use_kernel
                and self.lsh_mode == "asym" and self.doc_sig is not None):
            from repro.kernels.asym import ops as asym_ops
            dev = self._fused_device_arrays()
            idx, vals = asym_ops.asym_exp_topk(
                jnp.asarray(vecs, jnp.float32),
                self._kernel_sigs(self.doc_sig, "doc"),
                dev["planes"], self.bits, k,
                temperature=self.temperature)
            return (np.asarray(idx, np.int64),
                    np.asarray(vals, np.float64))
        sims = self._exp_sim_batch(vecs, self.doc_sig, self.doc_vecs, "doc")
        k = min(int(k), sims.shape[1])
        idx = np.argsort(-sims, axis=1, kind="stable")[:, :k]
        return idx.astype(np.int64), np.take_along_axis(sims, idx, axis=1)

    def word_shard_similarities_batch(
            self, word_ids: Sequence[int]) -> np.ndarray:
        """[n_words, n_shards] per-word p(w|s) rows in one pass — lets a
        batch of Boolean queries score all their distinct words with a
        single GEMM before applying the AND->product / OR->sum algebra."""
        ids = np.asarray(list(word_ids), np.int64)
        return self._exp_sim_batch(self.word_vecs[ids], self.shard_sig,
                                   self.shard_vecs, "shard")

    def _sum_docs_to_shards(self, doc_values: np.ndarray) -> np.ndarray:
        if self._doc_shard_ids is None:
            raise ValueError("doc-granular scoring requires attach_corpus()")
        out = np.zeros(self.shard_vecs.shape[0], np.float64)
        np.add.at(out, self._doc_shard_ids, doc_values)
        return out

    def _shard_sorted_docs(self):
        """Cached shard-sort structures for doc→shard reductions:
        (order, starts, counts, seg_sorted, sig_sorted) where ``order``
        permutes docs into shard-contiguous position, ``starts``/
        ``counts`` delimit each shard's segment in that order,
        ``seg_sorted`` is the int32 shard slot per sorted doc, and
        ``sig_sorted`` the doc signatures in sorted order (None when
        the index carries no doc signatures)."""
        if self._doc_shard_ids is None:
            raise ValueError("doc-granular scoring requires attach_corpus()")
        cache = getattr(self, "_shard_sort", None)
        if cache is None:
            ids = np.asarray(self._doc_shard_ids, np.int64)
            n_shards = self.shard_vecs.shape[0]
            order = np.argsort(ids, kind="stable")
            counts = np.bincount(ids, minlength=n_shards)
            starts = np.zeros(n_shards, np.int64)
            np.cumsum(counts[:-1], out=starts[1:])
            seg_sorted = ids[order].astype(np.int32)
            sig_sorted = (self.doc_sig[order]
                          if self.doc_sig is not None else None)
            cache = (order, starts, counts, seg_sorted, sig_sorted)
            object.__setattr__(self, "_shard_sort", cache)
        return cache

    def _sum_docs_to_shards_batch(self, doc_values: np.ndarray) -> np.ndarray:
        """[B, n_docs] -> [B, n_shards] row-wise scatter-add, vectorized
        as one ``np.add.reduceat`` over shard-sorted doc order — the
        non-kernel doc-granular hot path.  (The previous per-row
        ``np.bincount`` loop re-walked the doc→shard map B times;
        ``np.add.at`` with a 2-D fancy index is unbuffered and ~100x
        slower still.)  Empty shards need the same care as
        ``data/store.segment_sum_by_offsets``: reduceat mis-handles
        empty segments, so their slots are masked to zero."""
        order, starts, counts, _, _ = self._shard_sorted_docs()
        doc_values = np.atleast_2d(doc_values)
        n_docs = doc_values.shape[1]
        out = np.zeros((doc_values.shape[0], counts.shape[0]), np.float64)
        nonempty = counts > 0
        if n_docs == 0 or doc_values.shape[0] == 0 or not nonempty.any():
            return out
        # reduceat only at non-empty segment starts: those are strictly
        # increasing and in-bounds, so every slice is a real segment.
        # (Clamping empty starts into range instead would fold the last
        # docs of the preceding shard into the wrong slice whenever a
        # trailing shard is empty.)
        vals = np.ascontiguousarray(doc_values[:, order])
        out[:, nonempty] = np.add.reduceat(vals, starts[nonempty], axis=1)
        return out

    def megascan_payload(self, shard_ids, *, tm: int = 256):
        """Block-aligned packed signature payload for the one-launch
        megascan (kernels/megascan): the named shards' shard-sorted doc
        signatures, each padded independently to TM-row blocks and
        concatenated, with row -> shard-slot and row -> doc-id maps.
        Cached per ``(shard_ids, tm, content generation)`` — the serving
        path re-scans the same host groups every window, and the payload
        (like the fused device arrays) must not be re-uploaded per
        batch; the content axis in the key means an ``attach_corpus``
        content bump retires every stale payload without the cache dict
        having to be cleared by hand."""
        if self.doc_sig is None:
            raise ValueError("megascan requires doc signatures")
        from repro.kernels.megascan import ops as mega_ops
        ids = tuple(int(s) for s in shard_ids)
        key = (ids, int(tm), self.clock.current().content)
        cache = getattr(self, "_megascan_pay", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_megascan_pay", cache)
        payload = cache.get(key)
        if payload is None:
            order, starts, counts, _, sig_sorted = self._shard_sorted_docs()
            segments = [
                (sig_sorted[starts[s]:starts[s] + counts[s]],
                 order[starts[s]:starts[s] + counts[s]])
                for s in ids
            ]
            payload = mega_ops.build_payload(segments, tm=tm,
                                             shard_ids=ids)
            cache[key] = payload
        return payload

    def attach_corpus(self, corpus) -> "ApproxIndex":
        """Record the doc->shard map (needed for doc-granular scoring).
        Drops the shard-sort and device-array caches — both are derived
        from the map — and bumps the *content* generation: anything
        keyed on what this index answers from (semantic-cache entries,
        megascan payloads) is stale the moment a new corpus attaches.
        (Pre-PR-10 only the derived caches were dropped; a semantic
        cache fenced on placement alone would keep serving estimates
        computed over the old corpus.)"""
        self._doc_shard_ids = corpus.doc_shard_map()
        for cached in ("_shard_sort", "_fused_dev", "_megascan_pay"):
            if hasattr(self, cached):
                object.__delattr__(self, cached)
        self.clock.bump_content()
        return self

    def shard_probabilities(self, query_word_ids: Sequence[int]) -> np.ndarray:
        """phi_s(q) (paper Eq 11)."""
        return similarity_probabilities(self.shard_similarities(query_word_ids))

    def word_shard_similarity(self, word_id: int) -> np.ndarray:
        """p(w|s) up to constant for a single word (Boolean retrieval)."""
        return self._exp_sim(self.word_vecs[word_id], self.shard_sig,
                             self.shard_vecs, "shard")

    def vector_shard_similarities(self, vec: np.ndarray) -> np.ndarray:
        """exp-similarity of an arbitrary vector (e.g. a user vector) to
        every shard — used by recommendation."""
        return self._exp_sim(vec, self.shard_sig, self.shard_vecs, "shard")

    def vector_shard_similarities_batch(self, vecs: np.ndarray) -> np.ndarray:
        """[B, dim] arbitrary vectors -> [B, n_shards] exp-similarity."""
        return self._exp_sim_batch(vecs, self.shard_sig, self.shard_vecs,
                                   "shard")

    def vector_doc_similarities(self, vec: np.ndarray) -> np.ndarray:
        if self.doc_sig is None and self.doc_vecs is None:
            raise ValueError("index was built without document vectors")
        return self._exp_sim(vec, self.doc_sig, self.doc_vecs, "doc")

    # ------------------------------------------------------------------
    # persistence (atomic, manifest-checked)
    # ------------------------------------------------------------------
    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        payload = dict(
            word_vecs=self.word_vecs, shard_vecs=self.shard_vecs,
            planes=self.planes, word_sig=self.word_sig, shard_sig=self.shard_sig,
            doc_freq=self.doc_freq,
            meta=np.asarray(json.dumps(dict(
                bits=self.bits, n_docs=self.n_docs, avg_doc_len=self.avg_doc_len,
                use_lsh=self.use_lsh, has_docs=self.doc_vecs is not None,
                temperature=self.temperature, lsh_mode=self.lsh_mode,
                use_kernel=self.use_kernel, granularity=self.granularity,
                has_doc_shard_ids=self._doc_shard_ids is not None,
                has_center_mean=self.center_mean is not None,
            ))),
        )
        if self.doc_vecs is not None:
            payload["doc_vecs"] = self.doc_vecs
            payload["doc_sig"] = self.doc_sig
        if self._doc_shard_ids is not None:
            payload["doc_shard_ids"] = np.asarray(self._doc_shard_ids, np.int64)
        if self.center_mean is not None:
            payload["center_mean"] = np.asarray(self.center_mean, np.float32)
        atomic_savez(path, **payload)

    @staticmethod
    def load(path: str) -> "ApproxIndex":
        z = np.load(path, allow_pickle=False)
        meta = json.loads(str(z["meta"]))
        return ApproxIndex(
            word_vecs=z["word_vecs"], shard_vecs=z["shard_vecs"],
            doc_vecs=z["doc_vecs"] if meta["has_docs"] else None,
            planes=z["planes"], word_sig=z["word_sig"], shard_sig=z["shard_sig"],
            doc_sig=z["doc_sig"] if meta["has_docs"] else None,
            bits=meta["bits"], doc_freq=z["doc_freq"], n_docs=meta["n_docs"],
            avg_doc_len=meta["avg_doc_len"], use_lsh=meta["use_lsh"],
            temperature=meta.get("temperature", 1.0),
            lsh_mode=meta.get("lsh_mode", "sym"),
            # round-trip fidelity: a persisted doc-granular / kernel-routed
            # index used to silently revert to shard-granular numpy scoring
            use_kernel=meta.get("use_kernel", False),
            granularity=meta.get("granularity", "shard"),
            _doc_shard_ids=(z["doc_shard_ids"]
                            if meta.get("has_doc_shard_ids") else None),
            # pre-PR-10 saves lack the centering mean; such an index
            # still loads and serves — it just cannot host live ingest
            # with bit-consistent centering
            center_mean=(z["center_mean"]
                         if meta.get("has_center_mean") else None),
        )

    def nbytes(self) -> int:
        total = self.word_sig.nbytes + self.shard_sig.nbytes + self.planes.nbytes
        if self.doc_sig is not None:
            total += self.doc_sig.nbytes
        return total


def _doc_frequency(corpus: ShardedCorpus) -> np.ndarray:
    df = np.zeros(corpus.vocab_size, np.int64)
    for shard in corpus.shards:
        for doc in shard.iter_documents():
            df[np.unique(doc.tokens)] += 1
    return df


def _center_and_unit(x: np.ndarray, mean: np.ndarray) -> np.ndarray:
    y = x - mean
    n = np.linalg.norm(y, axis=-1, keepdims=True)
    return (y / np.maximum(n, 1e-8)).astype(np.float32)


def build_index(
    corpus: ShardedCorpus,
    model: pv.PVDBOWModel,
    lsh_cfg: Optional[lsh_mod.LSHConfig] = None,
    *,
    keep_doc_vectors: bool = True,
    use_lsh: bool = True,
    center: bool = True,
    temperature: float = 8.0,   # must match PVDBOWConfig.temperature
    lsh_mode: str = "asym",
    granularity: str = "shard",
) -> ApproxIndex:
    """Paper Fig. 2 step p2: compose shard vectors, hash everything.

    ``center`` applies the all-but-the-top style post-process: subtract
    the joint word/doc mean direction before re-normalizing.  SGNS with
    negative sampling leaves a large common offset (all docs repelled
    from the frequent-word direction); on unit vectors that offset
    pins every cosine near a constant and flattens phi_s.  Centering
    recovers the relative structure the sampler needs.  Set False for
    the strictly-paper-faithful ablation."""
    lsh_cfg = lsh_cfg or lsh_mod.LSHConfig()
    word_vecs = np.asarray(model.word_vecs, np.float32)
    doc_vecs = np.asarray(model.doc_vecs, np.float32)
    mean = None
    if center:
        mean = 0.5 * (word_vecs.mean(axis=0) + doc_vecs.mean(axis=0))
        word_vecs = _center_and_unit(word_vecs, mean)
        doc_vecs = _center_and_unit(doc_vecs, mean)
    shard_vecs = np.asarray(
        pv.shard_vectors(jnp.asarray(doc_vecs), corpus), np.float32)

    planes = np.asarray(lsh_mod.hyperplanes(lsh_cfg, word_vecs.shape[1]))
    jplanes = jnp.asarray(planes)

    def sign(x: np.ndarray) -> np.ndarray:
        return np.asarray(lsh_mod.pack_bits(
            lsh_mod.signature_bits(jnp.asarray(x), jplanes)))

    df = _doc_frequency(corpus)
    total_tokens = corpus.n_tokens
    return ApproxIndex(
        word_vecs=word_vecs,
        shard_vecs=shard_vecs,
        doc_vecs=doc_vecs if keep_doc_vectors else None,
        planes=planes,
        word_sig=sign(word_vecs),
        shard_sig=sign(shard_vecs),
        doc_sig=sign(doc_vecs) if keep_doc_vectors else None,
        bits=lsh_cfg.bits,
        doc_freq=df,
        n_docs=corpus.n_docs,
        avg_doc_len=total_tokens / max(corpus.n_docs, 1),
        use_lsh=use_lsh,
        temperature=temperature,
        lsh_mode=lsh_mode,
        granularity=granularity,
        _doc_shard_ids=corpus.doc_shard_map() if granularity == "doc" else None,
        center_mean=mean,
    )


def refresh_appended(
    index: ApproxIndex,
    corpus: ShardedCorpus,
    model: pv.PVDBOWModel,
    cfg: pv.PVDBOWConfig,
    appended_docs: Sequence[np.ndarray],
    affected_shards: Sequence[int],
    *,
    infer_steps: int = 50,
    infer_pause_s: float = 0.0,
) -> ApproxIndex:
    """Incremental index refresh for the live-ingest append path.

    ``corpus`` is the grown corpus (``ShardedCorpus.append_documents``),
    ``appended_docs`` the token arrays appended — in order, so their
    dense global ids start at ``index.n_docs`` — and
    ``affected_shards`` the shard ids whose membership changed.  New
    doc vectors come from *frozen-model* PV-DBOW inference (the word
    matrix fixed, ``pv_dbow.infer_doc_vectors``), pass through the
    identical centering transform the build applied
    (``index.center_mean``), and are signed on the numpy path —
    bit-identical to the jax signing of the build.  Only the affected
    shard centroids/signatures are recomputed (the same mean + sign
    ops as the build, so untouched rows are byte-identical and touched
    rows match a from-scratch rebuild); doc-frequency and length stats
    take exact integer deltas.  ``infer_pause_s`` is the writer's
    cooperative GIL yield between inference steps (result-neutral; see
    ``pv_dbow.infer_doc_vector``) so concurrent serving threads are
    never stalled for more than one dispatch.

    Returns a NEW ``ApproxIndex`` sharing the old one's generation
    clock — derived caches (sign matrices, fused device arrays,
    megascan payloads) start empty on the new object, and the *caller*
    bumps the content generation after swapping the new index in
    (swap-then-bump: a reader that races sees new refs under the old
    generation, which at worst inserts an immediately-stale cache
    entry, never serves one)."""
    if index.doc_vecs is None or index.doc_sig is None:
        raise ValueError("live refresh requires an index built with "
                         "keep_doc_vectors=True")
    k = len(appended_docs)
    if k == 0:
        return index
    if index.n_docs + k != corpus.n_docs:
        raise ValueError(
            f"appended docs do not line up: index has {index.n_docs}, "
            f"corpus has {corpus.n_docs}, appended {k}")
    vecs = pv.infer_doc_vectors(model, appended_docs, cfg,
                                steps=infer_steps, pause_s=infer_pause_s)
    if index.center_mean is not None:
        vecs = _center_and_unit(vecs, index.center_mean)
    else:
        vecs = np.asarray(vecs, np.float32)
    doc_vecs = np.concatenate([index.doc_vecs, vecs])
    doc_sig = np.concatenate(
        [index.doc_sig, lsh_mod.sign_vectors_np(vecs, index.planes)])

    old_shards = index.shard_vecs.shape[0]
    dim = index.shard_vecs.shape[1]
    shard_vecs = np.zeros((corpus.n_shards, dim), np.float32)
    shard_vecs[:old_shards] = index.shard_vecs
    touched = sorted({int(s) for s in affected_shards}
                     | set(range(old_shards, corpus.n_shards)))
    for sid in touched:
        # same op as the build path (pv.shard_vectors: numpy mean over
        # member doc vectors), so a touched row matches a full rebuild
        shard_vecs[sid] = doc_vecs[corpus.shards[sid].doc_ids].mean(axis=0)
    shard_sig = np.zeros((corpus.n_shards, index.shard_sig.shape[1]),
                         index.shard_sig.dtype)
    shard_sig[:old_shards] = index.shard_sig
    if touched:
        shard_sig[touched] = lsh_mod.sign_vectors_np(
            shard_vecs[np.asarray(touched)], index.planes)

    doc_freq = index.doc_freq.copy()
    for tokens in appended_docs:
        doc_freq[np.unique(np.asarray(tokens, np.int64))] += 1

    attach = (index.granularity == "doc"
              or index._doc_shard_ids is not None)
    new = dataclasses.replace(
        index,
        doc_vecs=doc_vecs, doc_sig=doc_sig,
        shard_vecs=shard_vecs, shard_sig=shard_sig,
        doc_freq=doc_freq, n_docs=corpus.n_docs,
        avg_doc_len=corpus.n_tokens / max(corpus.n_docs, 1),
        _doc_shard_ids=corpus.doc_shard_map() if attach else None,
    )
    # generation continuity: the new index answers under the same
    # authority; the ingest swap mints the content bump
    return new.use_clock(index.clock)
