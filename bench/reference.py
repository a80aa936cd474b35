"""The plain reference the served answers are compared with.

Flat scans over the benchmark's own copy of the corpus, in numpy and
float64, sharing no code with the program (no postings, no ``_scan``
twins, no estimators, no samplers):

* ``sig_wrong`` -- the index the reference works from, rebuilt from
  the trained PV-DBOW vectors: the joint word/doc mean subtracted and
  every vector set to unit length, shard vectors as the mean of their
  documents, hyperplanes drawn from the LSH seed the benchmark gave the
  build, and the sign of each projection.  It counts the program's
  signature bits that disagree with it where the projection lies
  farther from the hyperplane than rounding can reach.
* ``plan_scores`` -- the planner's shard scores, exp(beta * asym-cos)
  of every document summed per shard (counts, ranked) or of each word
  against the shard signatures under the AND -> product / OR -> sum
  algebra (Boolean), normalized into sampling probabilities.
* ``draw_stat`` -- how the draws a query got sit under the sampling
  law of its probabilities: with replacement for counts, distinct
  (successive sampling) for retrieval.
* ``count`` -- occurrences of a phrase in a shard, never across a
  document boundary, and the Hansen-Hurwitz estimate over the draws.
* ``boolean`` -- the documents of the sampled shards that satisfy the
  expression.
* ``ranked`` -- BM25 (k1 1.2, b 0.75, document frequencies over the
  whole corpus) of every document of the sampled shards, top k.

What it takes from the program: the trained PV-DBOW word and document
vectors, which play the part weights play for a served model (no
independent training could give the same vectors), the document ->
shard layout, which it checks is a partition of the corpus, and the
packed signatures, only to check them.

``plan_scores(..., dtype="bfloat16")`` is the control: the same
scoring with its matrix products on bfloat16 operands, the precision a
later change could be tempted to drop the planning kernel to.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence

import numpy as np

K1, B = 1.2, 0.75
FLOOR = 1e-6            # the sampler's floor on every shard's score
# a signature bit is decided only where the projection lies farther
# than this from the hyperplane (times the vector's length): float32
# and bfloat16 projections round by under a hundredth of it
MARGIN = 0.05


def _unpack_signs(sig: np.ndarray, bits: int) -> np.ndarray:
    """[M, bits/32] packed uint32 (bit j of word k is bit 32k + j) ->
    [M, bits] float64 in {-1, +1}."""
    b = np.unpackbits(np.ascontiguousarray(sig).view(np.uint8),
                      bitorder="little").reshape(sig.shape[0], -1)[:, :bits]
    return 2.0 * b.astype(np.float64) - 1.0


def _pack_signs(bits: np.ndarray) -> np.ndarray:
    """[M, bits] bool -> [M, bits/32] uint32, the layout above."""
    return np.ascontiguousarray(np.packbits(
        bits, axis=1, bitorder="little")).view(np.uint32)


def _unit(x: np.ndarray) -> np.ndarray:
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-8)


def _probabilities(scores: np.ndarray) -> np.ndarray:
    s = np.maximum(scores, 0.0) + FLOOR
    return s / s.sum(axis=-1, keepdims=True)


class Reference:
    """Exact answers over ``corpus`` (``bench.gen.corpus.Corpus``) laid
    out in shards as ``shard_docs`` (doc ids of each shard, in order)."""

    def __init__(self, corpus, shard_docs: Sequence[np.ndarray], tables: Dict):
        self.tokens = corpus.tokens
        self.offsets = corpus.offsets
        self.vocab = corpus.vocab_size
        self.n_docs = corpus.n_docs
        self.shard_docs = [np.asarray(d, np.int64) for d in shard_docs]
        seen = np.concatenate(self.shard_docs)
        if not np.array_equal(np.sort(seen), np.arange(self.n_docs)):
            raise ValueError("the shard layout is not a partition of the "
                             "corpus's documents")
        self.doc_shard = np.empty(self.n_docs, np.int64)
        for s, d in enumerate(self.shard_docs):
            self.doc_shard[d] = s
        self.t = tables
        self._shards: Dict[int, tuple] = {}
        self._df = None
        self._index = None

    @property
    def n_shards(self) -> int:
        return len(self.shard_docs)

    # ------------------------------------------------------------------
    # shard contents
    # ------------------------------------------------------------------
    def shard(self, s: int):
        """(tokens, per-token local doc index, doc lengths) of shard s."""
        got = self._shards.get(s)
        if got is None:
            d = self.shard_docs[s]
            lens = self.offsets[d + 1] - self.offsets[d]
            local = np.repeat(np.arange(len(d)), lens)
            starts = np.zeros(len(d), np.int64)
            np.cumsum(lens[:-1], out=starts[1:])
            idx = np.repeat(self.offsets[d] - starts, lens) + np.arange(
                int(lens.sum()), dtype=np.int64)
            got = (self.tokens[idx], local, lens)
            self._shards[s] = got
        return got

    def count_in_shard(self, s: int, phrase: Sequence[int]) -> int:
        tok, local, _ = self.shard(s)
        k = len(phrase)
        if len(tok) < k:
            return 0
        n = len(tok) - k + 1
        hit = tok[:n] == phrase[0]
        for j in range(1, k):
            hit &= tok[j:j + n] == phrase[j]
        pos = np.nonzero(hit)[0]
        return int(np.count_nonzero(local[pos] == local[pos + k - 1]))

    def tf(self, s: int, word: int) -> np.ndarray:
        tok, local, lens = self.shard(s)
        return np.bincount(local[tok == word], minlength=len(lens))

    # ------------------------------------------------------------------
    # whole-corpus statistics
    # ------------------------------------------------------------------
    def doc_freq(self) -> np.ndarray:
        """Number of documents that hold each word."""
        if self._df is None:
            doc = np.repeat(np.arange(self.n_docs, dtype=np.int64),
                            np.diff(self.offsets))
            pairs = np.unique(doc * self.vocab + self.tokens)
            self._df = np.bincount(pairs % self.vocab, minlength=self.vocab)
        return self._df

    # ------------------------------------------------------------------
    # the index, rebuilt from the trained vectors
    # ------------------------------------------------------------------
    def index(self) -> Dict[str, Any]:
        """Unit centred word vectors, hyperplanes, and the doc and shard
        signatures (packed), with ``sig_wrong``: program signature bits
        that disagree where the projection decides the bit."""
        if self._index is not None:
            return self._index
        import jax
        import jax.numpy as jnp
        t = self.t
        wv = t["word_vecs"].astype(np.float64)
        dv = t["doc_vecs"].astype(np.float64)
        mean = 0.5 * (wv.mean(axis=0) + dv.mean(axis=0))
        wv, dv = _unit(wv - mean), _unit(dv - mean)
        planes = np.asarray(jax.random.normal(
            jax.random.PRNGKey(t["lsh_seed"]), (t["bits"], dv.shape[1]),
            jnp.float32), np.float64)
        sv = np.stack([dv[d].mean(axis=0) if len(d) else
                       np.zeros(dv.shape[1]) for d in self.shard_docs])
        wrong = 0

        def sign(vecs, program):
            nonlocal wrong
            out = np.empty_like(program)
            for lo in range(0, len(vecs), 65536):
                v = vecs[lo:lo + 65536]
                proj = v @ planes.T
                near = np.abs(proj) < MARGIN * np.linalg.norm(
                    v, axis=-1, keepdims=True)
                theirs = _unpack_signs(program[lo:lo + 65536],
                                       t["bits"]) > 0
                ours = proj >= 0
                wrong += int(np.count_nonzero((ours != theirs) & ~near))
                # where rounding decides the bit, the program's bit stands
                out[lo:lo + 65536] = _pack_signs(np.where(near, theirs, ours))
            return out

        doc_sig = sign(dv, t["doc_sig"])
        shard_sig = sign(sv, t["shard_sig"])
        self._index = dict(word_vecs=wv, planes=planes, doc_sig=doc_sig,
                           shard_sig=shard_sig, sig_wrong=wrong)
        return self._index

    # ------------------------------------------------------------------
    # planning
    # ------------------------------------------------------------------
    def _project(self, vecs: np.ndarray, dtype: str):
        v = np.asarray(vecs, np.float64)
        q = v / np.maximum(np.linalg.norm(v, axis=-1, keepdims=True), 1e-9)
        planes = self.index()["planes"]
        if dtype == "float64":
            return q @ planes.T
        return np.asarray(_bf16_dot(q, planes.T), np.float64)

    def _exp_cos(self, proj: np.ndarray, signs: np.ndarray,
                 dtype: str) -> np.ndarray:
        bits = self.t["bits"]
        scale = 1.0 / (bits * math.sqrt(2.0 / math.pi))
        if dtype == "float64":
            dots = proj @ signs.T
        else:
            dots = np.asarray(_bf16_dot(proj, signs.T), np.float64)
        return np.exp(self.t["temperature"] * np.clip(dots * scale, -1, 1))

    def plan_scores(self, queries: Sequence[tuple],
                    dtype: str = "float64") -> np.ndarray:
        """[Q, n_shards] sampling probabilities the planner should give
        ``queries`` (the tuples of ``bench.gen.queries``)."""
        ix = self.index()
        out = np.zeros((len(queries), self.n_shards), np.float64)
        vec_rows = [i for i, q in enumerate(queries) if q[0] != "bool"]
        if vec_rows:
            vecs = np.stack([ix["word_vecs"][list(queries[i][1])].sum(0)
                             for i in vec_rows])
            proj = self._project(vecs, dtype)
            sums = np.zeros((len(vec_rows), self.n_shards), np.float64)
            sig = ix["doc_sig"]
            for lo in range(0, self.n_docs, 65536):
                hi = min(self.n_docs, lo + 65536)
                sims = self._exp_cos(proj, _unpack_signs(
                    sig[lo:hi], self.t["bits"]), dtype)
                seg = self.doc_shard[lo:hi]
                for r in range(len(vec_rows)):
                    sums[r] += np.bincount(seg, weights=sims[r],
                                           minlength=self.n_shards)
            out[vec_rows] = sums
        bool_rows = [i for i, q in enumerate(queries) if q[0] == "bool"]
        if bool_rows:
            words = sorted({w for i in bool_rows for w in queries[i][1][1:]})
            proj = self._project(ix["word_vecs"][words], dtype)
            rows = dict(zip(words, self._exp_cos(proj, _unpack_signs(
                ix["shard_sig"], self.t["bits"]), dtype)))
            for i in bool_rows:
                form, *w = queries[i][1]
                out[i] = (rows[w[0]] * rows[w[1]] if form == "and"
                          else (rows[w[0]] + rows[w[1]]) * rows[w[2]])
        return _probabilities(out)

    # ------------------------------------------------------------------
    # the sampling law
    # ------------------------------------------------------------------
    @staticmethod
    def draw_stat(p: np.ndarray, draws: np.ndarray, distinct: bool,
                  rng: np.random.Generator, sims: int = 256):
        """(s, mean, var): s is the sum of log(n p) over ``draws``; mean
        and var are its moments under the law that draws len(draws)
        shards from ``p`` (n of them): independent draws, or with
        ``distinct`` successive draws without replacement, whose moments
        come from ``sims`` draws of the reference's own (Gumbel top-k)."""
        lp = np.log(len(p) * p)
        m = len(draws)
        s = float(lp[draws].sum())
        if not distinct:
            mu = float(p @ lp)
            return s, m * mu, m * float(p @ (lp - mu) ** 2)
        keys = np.log(p) + rng.gumbel(size=(sims, len(p)))
        top = np.argpartition(-keys, m - 1, axis=1)[:, :m]
        sim = lp[top].sum(axis=1)
        return s, float(sim.mean()), float(sim.var())

    # ------------------------------------------------------------------
    # answers over a given sample
    # ------------------------------------------------------------------
    def count(self, phrase: Sequence[int], draws: np.ndarray,
              probabilities: np.ndarray) -> float:
        """Hansen-Hurwitz estimate of the phrase's corpus count from the
        with-replacement ``draws`` taken with ``probabilities``."""
        per = {int(s): self.count_in_shard(int(s), phrase)
               for s in np.unique(draws)}
        local = np.asarray([per[int(s)] for s in draws], np.float64)
        return float((local / probabilities[draws]).mean())

    def boolean(self, form: tuple, shards: np.ndarray) -> np.ndarray:
        """Sorted doc ids of ``shards`` that satisfy ``form``."""
        kind, *w = form
        out: List[np.ndarray] = []
        for s in np.unique(shards):
            has = [self.tf(int(s), x) > 0 for x in w]
            m = (has[0] & has[1] if kind == "and"
                 else (has[0] | has[1]) & has[2])
            out.append(self.shard_docs[int(s)][m])
        return np.unique(np.concatenate(out)) if out else np.zeros(0, np.int64)

    def ranked(self, words: Sequence[int], k: int, shards: np.ndarray):
        """(doc ids, scores) of the BM25 top k over ``shards``, ties in
        shard order then document order."""
        df = self.doc_freq()
        avg_len = len(self.tokens) / max(self.n_docs, 1)
        ids, scores = [], []
        for s in np.unique(shards):
            s = int(s)
            lens = self.shard(s)[2].astype(np.float64)
            norm = K1 * (1.0 - B + B * lens / max(avg_len, 1e-9))
            sc = np.zeros(len(lens), np.float64)
            for w in words:
                tf = self.tf(s, w).astype(np.float64)
                d = float(df[w])
                idf = np.log(1.0 + (self.n_docs - d + 0.5) / (d + 0.5))
                sc += idf * tf * (K1 + 1.0) / np.maximum(tf + norm, 1e-9)
            ids.append(self.shard_docs[s])
            scores.append(sc)
        if not ids:
            return np.zeros(0, np.int64), np.zeros(0, np.float64)
        ids, scores = np.concatenate(ids), np.concatenate(scores)
        top = np.argsort(-scores, kind="stable")[:k]
        return ids[top], scores[top]


class ShardView:
    """Shard ``s`` of ``ref`` as the phrase picker reads it: ``tokens``,
    document ``offsets`` within the shard, ``n_tokens``; built on first
    use."""

    def __init__(self, ref: Reference, s: int):
        self._ref, self._s = ref, s
        d = ref.shard_docs[s]
        self.n_tokens = int((ref.offsets[d + 1] - ref.offsets[d]).sum())

    @property
    def tokens(self) -> np.ndarray:
        return self._ref.shard(self._s)[0]

    @property
    def offsets(self) -> np.ndarray:
        lens = self._ref.shard(self._s)[2]
        return np.concatenate([[0], np.cumsum(lens)])


def phrase_counter(corpus):
    """An exact corpus-wide phrase counter over ``corpus``: the
    positions of the phrase's rarest word, checked in full and never
    across a document boundary."""
    tokens, offsets = corpus.tokens, corpus.offsets
    order = np.argsort(tokens, kind="stable")
    starts = np.searchsorted(tokens[order],
                             np.arange(corpus.vocab_size + 1))

    def count(phrase) -> int:
        k = len(phrase)
        j = int(np.argmin([starts[w + 1] - starts[w] for w in phrase]))
        pos = order[starts[phrase[j]]:starts[phrase[j] + 1]] - j
        pos = pos[(pos >= 0) & (pos + k <= len(tokens))]
        for i, w in enumerate(phrase):
            pos = pos[tokens[pos + i] == w]
        doc = np.searchsorted(offsets, pos, side="right")
        return int(np.count_nonzero(
            doc == np.searchsorted(offsets, pos + k - 1, side="right")))
    return count


def _bf16_dot(a: np.ndarray, b: np.ndarray):
    """a @ b with both operands rounded to bfloat16 and float32
    accumulation: a matrix product at the TPU's default precision."""
    import jax.numpy as jnp
    return jnp.dot(jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16),
                   preferred_element_type=jnp.float32)
