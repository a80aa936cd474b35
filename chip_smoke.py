"""Chip smoke test: the EmApprox build-and-serve path on one TPU chip.

    python chip_smoke.py [--seed 0]

One process, one chip, everything generated from ``--seed``:

  a. device check — JAX must find a TPU; there is no CPU fallback;
  b. build — a 200k-document synthetic corpus (32k vocabulary, ~1,950
     shards of 16k tokens), PV-DBOW dim 100 trained with the negsamp
     kernel, k-means allocation with the kmeans kernel, and the
     doc-granular, kernel-backed 256-bit LSH index;
  c. kernel parity — every Pallas kernel against its ``ref.py`` oracle
     at those widths, compiled for the chip (not interpreted), at the
     interpret-mode tests' tolerances; the megascan DMA schedule must
     equal the streamed one bit for bit;
  d. serve — 64 mixed queries (phrase counts, Boolean, ranked top-10)
     through ``build_serving_stack``'s ``BatchWindow`` at rate 0.1,
     then one batch at rate 1.0 that must equal independent flat-scan
     references exactly;
  e. megascan through the executor — one launch over the whole fleet
     must equal the per-shard launches bit for bit;
  f. live ingest — one ``Ingestor.step`` of 1,000 documents carrying a
     sentinel phrase, which a rate-1.0 count must then see in full.

Any executor retry, lost shard, shed query or ingest error fails the
run.  A failed check or phase is reported and the run goes on to the
next, so one run shows every mismatch; the exit code is still non-zero.
Lines marked ``smoke timing`` / ``smoke parity`` are smoke
figures, not benchmark metrics.  The last line of standard output is
one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every phase passed; any failure exits non-zero without it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The smoke's widths: dim 100 is the paper's lambda_1; 256-bit
    signatures are W = 8 words; 200k documents of mean length 160 cut
    into 16k-token shards give ~1,950 shard slots."""
    n_docs: int = 200_000
    vocab: int = 32_768
    shard_tokens: int = 16_384
    dim: int = 100
    temperature: float = 8.0
    train_steps: int = 2_000
    batch_pairs: int = 8_192
    bits: int = 256
    n_queries: int = 64
    parity_queries: int = 32
    mega_queries: int = 8
    ingest_docs: int = 1_000
    ingest_doc_len: int = 32
    ingest_infer_steps: int = 20


def say(tag: str, msg: str) -> None:
    print(f"smoke {tag}: {msg}", flush=True)


class Compiles:
    """Counts backend compiles (and persistent-cache hits) per phase."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str, *, fatal: bool = False):
        """Times a phase.  A failure in it is recorded and the run goes
        on to the next phase, unless ``fatal`` (later phases need its
        result)."""
        n0, s0, h0 = self.n, self.seconds, self.cache_hits
        t0 = time.perf_counter()
        try:
            yield
        except Exception:  # noqa: BLE001 - recorded; fails the run at the end
            if fatal:
                raise
            traceback.print_exc()
            FAILED.append(name)
            say("FAILED", f"phase {name}")
        say("timing", f"{name} {time.perf_counter() - t0:.3f} s wall, "
                      f"{self.n - n0} compiles ({self.seconds - s0:.3f} s), "
                      f"{self.cache_hits - h0} compile-cache hits")


#: Names of the checks and phases that failed.  A failed check is
#: reported and the run goes on, so that one run on the chip shows every
#: mismatch; any entry fails the smoke at the end.
FAILED: list = []


def _record(name: str, check) -> None:
    try:
        check()
    except AssertionError as e:
        FAILED.append(name)
        say("FAILED", f"{name}: {e}")


def close(name: str, got, want, *, rtol: float, atol: float = 0.0) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = np.abs(got - want)
    # the largest error where it is judged by rtol, and by atol elsewhere
    rel = np.where(atol < rtol * np.abs(want),
                   diff / np.maximum(np.abs(want), 1e-30), 0.0)
    say("parity", f"{name}: shape {tuple(got.shape)}, max rel err "
                  f"{rel.max(initial=0.0):.3e} (rtol {rtol}), max abs err "
                  f"{diff.max(initial=0.0):.3e} (atol {atol})")
    _record(name, lambda: np.testing.assert_allclose(
        got, want, rtol=rtol, atol=atol, err_msg=name))


def equal(name: str, a, b) -> None:
    say("parity", f"{name}: shape {np.shape(a)}, bit-for-bit")
    _record(name, lambda: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b), err_msg=name))


# ----------------------------------------------------------------------
# b. build
# ----------------------------------------------------------------------
def build(s: Sizes, seed: int):
    from repro.core.allocation import KMeansConfig, allocate_corpus
    from repro.core.index import build_index
    from repro.core.lsh import LSHConfig
    from repro.core.pv_dbow import PVDBOWConfig, train_pv_dbow
    from repro.data.corpus import SyntheticCorpusConfig, generate_text_corpus
    from repro.data.store import ShardedCorpus

    docs, _ = generate_text_corpus(SyntheticCorpusConfig(
        n_docs=s.n_docs, vocab_size=s.vocab, seed=seed))
    corpus = ShardedCorpus.from_documents(docs, s.vocab,
                                          shard_tokens=s.shard_tokens)
    del docs
    pcfg = PVDBOWConfig(dim=s.dim, temperature=s.temperature,
                        steps=s.train_steps, batch_pairs=s.batch_pairs,
                        seed=seed, use_kernel=True)
    losses = []
    model = train_pv_dbow(corpus, pcfg,
                          callback=lambda step, loss: losses.append(loss))
    if not np.isfinite(losses).all():
        raise RuntimeError(f"PV-DBOW loss is not finite: {losses}")
    pre = build_index(corpus, model, LSHConfig(bits=s.bits), use_lsh=False,
                      temperature=s.temperature)
    corpus = allocate_corpus(corpus, pre.doc_vecs, cfg=KMeansConfig(
        n_clusters=corpus.n_shards, seed=seed, use_kernel=True))
    index = build_index(corpus, model, LSHConfig(bits=s.bits),
                        temperature=s.temperature)
    # the served index: doc-granular planning through the fused kernels
    index = dataclasses.replace(index, granularity="doc",
                                use_kernel=True).attach_corpus(corpus)
    for name in ("word_vecs", "doc_vecs", "shard_vecs"):
        if not np.isfinite(getattr(index, name)).all():
            raise RuntimeError(f"index {name} is not finite")
    say("figure", f"corpus {corpus.n_docs} docs, {corpus.n_tokens} tokens, "
                  f"{corpus.n_shards} shards; PV-DBOW loss "
                  f"{losses[0]:.4f} -> {losses[-1]:.4f} over {s.train_steps} "
                  f"steps of {s.batch_pairs} pairs; index "
                  f"{index.nbytes() / 2**20:.1f} MiB of signatures")
    return corpus, model, pcfg, index


# ----------------------------------------------------------------------
# c. kernel parity
# ----------------------------------------------------------------------
def kernel_parity(s: Sizes, corpus, model, index, rng) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.common import pick_query_words
    from repro.core import lsh as lsh_mod
    from repro.core.pv_dbow import sample_negatives
    from repro.kernels.asym import ops as aops, ref as aref
    from repro.kernels.common import interpret_mode
    from repro.kernels.hamming import ops as hops, ref as href
    from repro.kernels.kmeans import ops as kops, ref as kref
    from repro.kernels.megascan import ops as mops, ref as mref
    from repro.kernels.negsamp import ops as nops, ref as nref

    say("parity", f"Pallas kernels interpreted: {interpret_mode()}")
    # the oracles run as XLA programs: full float32 matmuls, as in the
    # kernels, so both sides compute the same float32 math
    oracle = jax.default_matmul_precision("highest")
    bits, beta, n_shards = index.bits, index.temperature, corpus.n_shards
    words = pick_query_words(corpus, 3 * s.parity_queries, rng)
    q = jnp.asarray(index.query_vectors(
        words[:3 * s.parity_queries].reshape(-1, 3).tolist()), jnp.float32)
    # the kernels read the index's word-major device signatures; the
    # oracles read the same signatures row-major ([M, W])
    dev = index._fused_device_arrays()
    planes, sig, layout = dev["planes"], dev["sig"], dev["layout"]
    doc_sig = index._kernel_sigs(index.doc_sig, "doc")
    _, _, _, seg, sig_rows = index._shard_sorted_docs()
    seg, sig_rows = jnp.asarray(seg), jnp.asarray(sig_rows)
    doc_rows = jnp.asarray(index.doc_sig)

    with oracle:
        sims = aref.asym_exp_similarity_ref(q, doc_rows, planes, bits, beta)
        want_seg = aref.asym_exp_segment_sum_ref(q, sig_rows, planes, bits,
                                                 seg, n_shards, beta)
    close("asym sim", aops.asym_exp_similarity(q, doc_sig, planes, bits,
                                               temperature=beta),
          sims, rtol=3e-5)
    close("asym segsum", aops.asym_exp_layout_sum(
        q, sig, planes, bits, layout, n_shards, temperature=beta),
        want_seg, rtol=1e-4)
    idx, vals = aops.asym_exp_topk(q, doc_sig, planes, bits, 10,
                                   temperature=beta)
    want_vals = jax.lax.top_k(sims, 10)[0]
    close("asym top-k values", vals, want_vals, rtol=1e-4)
    close("asym top-k ids (oracle score of each)",
          np.take_along_axis(np.asarray(sims), np.asarray(idx), 1), vals,
          rtol=1e-5)

    qsig = lsh_mod.pack_bits(lsh_mod.signature_bits(q, planes))
    with oracle:
        dist = np.asarray(href.hamming_distance_ref(qsig, doc_rows))
        want_hseg = href.hamming_segment_similarity_ref(
            qsig, sig_rows, bits, seg, n_shards, beta)
    equal("hamming distance", hops.hamming_distance(qsig, doc_sig), dist)
    # exp(beta*cos(pi*m/L)) of the oracle's exact distances in float64:
    # the oracle's own float32 exp(cos) ** beta is off by up to 3.1e-5
    # on the chip (its exp's 3.7e-6, raised to the 8th power), more
    # than the kernel (5.1e-6) and than the tolerance
    hsims = np.exp(beta * np.cos(np.pi * dist.astype(np.float64) / bits))
    close("hamming sim", hops.hamming_similarity(qsig, doc_sig, bits,
                                                 temperature=beta),
          hsims, rtol=2e-5)
    close("hamming segsum", hops.hamming_segment_similarity(
        qsig, sig, bits, seg, n_shards, temperature=beta),
        want_hseg, rtol=1e-4)

    # megascan over the whole fleet, both data-movement schedules
    payload = index.megascan_payload(range(n_shards))
    qm = q[:s.mega_queries]
    say("figure", f"megascan payload {payload.n_blocks} blocks of "
                  f"{payload.tm} rows, {payload.n_slots} slots, "
                  f"{payload.nbytes_streamed / 2**20:.1f} MiB streamed")
    for mode, qx in (("asym", qm), ("hamming", qsig[:s.mega_queries])):
        dma, streamed = (mops.megascan_segment_sums(
            payload, qx, planes, bits, mode=mode, temperature=beta,
            double_buffer=db) for db in (True, False))
        equal(f"megascan {mode} segsum DMA vs streamed", dma, streamed)
        with oracle:
            want = mref.megascan_segment_sums_ref(
                payload, qx, planes, bits, mode=mode, temperature=beta)
        close(f"megascan {mode} segsum DMA", dma, want, rtol=1e-4)
    ids_d, vals_d = mops.megascan_topk(payload, qm, planes, bits, 10,
                                       temperature=beta, double_buffer=True)
    ids_s, vals_s = mops.megascan_topk(payload, qm, planes, bits, 10,
                                       temperature=beta, double_buffer=False)
    equal("megascan top-k ids DMA vs streamed", ids_d, ids_s)
    equal("megascan top-k values DMA vs streamed", vals_d, vals_s)
    with oracle:
        _, rvals = mref.megascan_topk_ref(payload, qm, planes, bits, 10,
                                          temperature=beta)
    finite = np.isfinite(rvals)
    equal("megascan top-k filled slots", np.isfinite(vals_d), finite)
    close("megascan top-k values", vals_d[finite], rvals[finite], rtol=1e-4)
    picked = np.take_along_axis(
        np.asarray(sims[:s.mega_queries])[:, None, :],
        np.where(ids_d >= 0, ids_d, 0), 2)
    close("megascan top-k ids (oracle score of each)", picked[finite],
          vals_d[finite], rtol=1e-5)

    # negsamp: one training batch at the build's widths
    kd, kw, kn = jax.random.split(jax.random.PRNGKey(s.dim), 3)
    n_docs, vocab = model.doc_vecs.shape[0], model.word_vecs.shape[0]
    d = model.doc_vecs[jax.random.randint(kd, (s.batch_pairs,), 0, n_docs)]
    w = model.word_vecs[jax.random.randint(kw, (s.batch_pairs,), 0, vocab)]
    cdf = jnp.linspace(1.0 / vocab, 1.0, vocab)
    wn = model.word_vecs[sample_negatives(kn, cdf, (s.batch_pairs, 5))]
    got = nops.negsamp_grads(d, w, wn, temperature=beta)
    with oracle:
        want = nref.negsamp_grads_ref(d, w, wn, temperature=beta)
    for name, g, r in zip(("loss", "grad_d", "grad_w", "grad_wn"), got, want):
        close(f"negsamp {name}", g, r, rtol=1e-5, atol=1e-6)

    # kmeans assignment of every document to the shard centroids
    x = jnp.asarray(index.doc_vecs)
    c = jnp.asarray(index.shard_vecs)
    c = c / jnp.maximum(jnp.linalg.norm(c, axis=1, keepdims=True), 1e-8)
    got_a, got_s = kops.assign_with_scores(x, c)
    with oracle:
        want_a, want_s = kref.assign_ref(x, c)
        scores = np.asarray(x @ c.T)
    close("kmeans best score", got_s, want_s, rtol=1e-5)
    got_a, want_a = np.asarray(got_a), np.asarray(want_a)
    rows = np.nonzero(got_a != want_a)[0]
    # a differing pick must be an exact-arithmetic tie: both centroids
    # score the document the same to float32 rounding
    gap = np.abs(scores[rows, got_a[rows]] - scores[rows, want_a[rows]])
    say("parity", f"kmeans assign: {x.shape[0] - rows.size}/{x.shape[0]} "
                  f"identical; {rows.size} ties, largest score gap "
                  f"{float(gap.max()) if rows.size else 0.0:.3e}")
    if rows.size and gap.max() > 1e-6:
        raise AssertionError(f"kmeans assignment differs beyond ties at "
                             f"{rows[:10].tolist()}")


# ----------------------------------------------------------------------
# d. serve
# ----------------------------------------------------------------------
def mixed_queries(s: Sizes, corpus, rng):
    from benchmarks.common import pick_query_phrases, pick_query_words
    from repro.core.queries import BatchQuery, parse_boolean

    n = s.n_queries
    phrases = pick_query_phrases(corpus, n, rng, min_count=0)
    words = pick_query_words(corpus, 3 * n, rng)
    out = []
    for i in range(n):
        w = [int(x) for x in words[3 * i:3 * i + 3]]
        kind = i % 4
        if kind in (0, 1):
            out.append(BatchQuery.count(phrases[i]))
        elif kind == 2:
            expr = ([w[0], "and", w[1]] if i % 8 == 2
                    else [w[0], "or", w[1], "and", w[2]])
            out.append(BatchQuery.boolean(parse_boolean(expr)))
        else:
            out.append(BatchQuery.ranked(w, k=10))
    return out


def reference(q, corpus, index):
    """The exact answer from flat scans that share no code with the
    batch engine's postings path."""
    from repro.core.queries.retrieval import (_expr_eval_docs_scan,
                                              bm25_scores_for_shard_scan)
    from repro.data.store import docs_matching_all_scan

    if q.kind == "count":
        return corpus.count_phrase(q.phrase)
    if q.kind == "bool":
        e = q.expr
        if e.op == "and" and e.left.op == "word" and e.right.op == "word":
            parts = [docs_matching_all_scan(sh, [e.left.word, e.right.word])
                     for sh in corpus.shards]
        else:
            parts = [sh.doc_ids[_expr_eval_docs_scan(e, sh)]
                     for sh in corpus.shards]
        return np.unique(np.concatenate(parts))
    ids = np.concatenate([sh.doc_ids for sh in corpus.shards])
    sc = np.concatenate([bm25_scores_for_shard_scan(
        sh, q.words, index.doc_freq, index.n_docs, index.avg_doc_len)
        for sh in corpus.shards])
    return ids, sc


def check_exact(queries, results, corpus, index) -> dict:
    from repro.core.queries import precision_at_k, recall

    rel, rec, p10 = [], [], []
    for i, (q, r) in enumerate(zip(queries, results)):
        ref = reference(q, corpus, index)
        if q.kind == "count":
            rel.append(abs(r.estimate.value - ref) / max(ref, 1))
            if r.estimate.value != ref:
                raise AssertionError(f"query {i}: count {r.estimate.value} "
                                     f"!= exact {ref}")
        elif q.kind == "bool":
            rec.append(recall(r.doc_ids, ref))
            np.testing.assert_array_equal(np.sort(r.doc_ids), ref,
                                          err_msg=f"query {i}")
        else:
            ids, sc = ref
            top = np.argsort(-sc, kind="stable")[:q.k]
            p10.append(precision_at_k(r.doc_ids, ids[top], q.k))
            score_of = dict(zip(ids.tolist(), sc.tolist()))
            # exact BM25 scores of the returned docs, and the same
            # top-k score list (ties at the cut may order differently)
            np.testing.assert_array_equal(
                [score_of[d] for d in r.doc_ids.tolist()], r.scores)
            np.testing.assert_array_equal(r.scores, sc[top])
    figs = dict(max_rel_err=max(rel), min_recall=min(rec), min_p10=min(p10))
    if figs != dict(max_rel_err=0.0, min_recall=1.0, min_p10=1.0):
        raise AssertionError(f"rate-1.0 answers are not exact: {figs}")
    return figs


def served_figures(queries, results, precise) -> str:
    from repro.core.queries import precision_at_k, recall

    rel, rec, p10 = [], [], []
    for q, r, p in zip(queries, results, precise):
        if q.kind == "count":
            if not np.isfinite(r.estimate.value) or r.estimate.value < 0:
                raise AssertionError(f"bad estimate {r.estimate.value}")
            if p.estimate.value:
                rel.append(abs(r.estimate.value - p.estimate.value)
                           / p.estimate.value)
        elif q.kind == "bool":
            if not np.isin(r.doc_ids, p.doc_ids).all():
                raise AssertionError("sampled Boolean answer holds a doc "
                                     "the exact answer does not")
            rec.append(recall(r.doc_ids, p.doc_ids))
        else:
            p10.append(precision_at_k(r.doc_ids, p.doc_ids, q.k))
    return (f"count mean rel err {np.mean(rel):.3f}, Boolean mean recall "
            f"{np.mean(rec):.3f}, ranked mean P@10 {np.mean(p10):.3f}")


def assert_clean(stack) -> None:
    ex = stack.executor.stats
    win = stack.window.stats
    bad = {k: v for k, v in (("executor retries", ex["retries"]),
                             ("lost shards", ex["lost_shards"]),
                             ("shed queries", win["shed"]),
                             ("window batch retries", win["batch_retries"]))
           if v}
    if bad:
        raise AssertionError(f"serving absorbed failures: {bad}")


def serve(s: Sizes, stack, corpus, index, queries):
    # the planner's shard scores: the index's fused-kernel route (what
    # the engine plans with) against its unfused route on the same
    # queries, at the segment-sum tolerance
    words = [q.word_ids() for q in queries if q.kind != "bool"]
    close("planner shard scores, fused vs unfused",
          index.shard_similarities_batch(words, fused=True),
          index.shard_similarities_batch(words, fused=False), rtol=1e-4)
    futs = [stack.window.submit(q) for q in queries]
    sampled = [f.result(timeout=600) for f in futs]
    precise = stack.engine.execute(queries, 1.0)
    figs = check_exact(queries, precise, corpus, index)
    assert_clean(stack)
    say("figure", f"rate 0.1 through the window: "
                  f"{served_figures(queries, sampled, precise)}; "
                  f"{stack.window.stats['batches']} windows")
    say("parity", f"rate 1.0 vs flat-scan references: {figs}")


# ----------------------------------------------------------------------
# e. megascan through the executor
# ----------------------------------------------------------------------
def megascan_executor(s: Sizes, stack, corpus, index, rng) -> None:
    from benchmarks.common import pick_query_words
    from repro.kernels.megascan import MegascanSpec

    words = pick_query_words(corpus, 3 * s.mega_queries, rng)
    vecs = index.query_vectors(words.reshape(-1, 3).tolist())
    plan = [list(range(corpus.n_shards))] * len(vecs)
    ex = stack.executor
    for label, spec in (("sum", MegascanSpec(index, vecs)),
                        ("ranked", MegascanSpec(index, vecs, ranked_k=10))):
        fns = spec.scan_fns()
        mega = ex.map_shard_batch(corpus, plan, fns, megakernel=True)
        per = ex.map_shard_batch(corpus, plan, fns, megakernel=False)
        for qi, (m, p) in enumerate(zip(mega, per)):
            if m.keys() != p.keys():
                raise AssertionError(f"{label} query {qi}: shard sets differ")
            for sid in m:
                if label == "sum":
                    same = m[sid] == p[sid]
                else:
                    same = all(np.array_equal(m[sid][k], p[sid][k])
                               for k in ("doc_ids", "values"))
                if not same:
                    raise AssertionError(f"megascan {label} query {qi} "
                                         f"shard {sid} differs")
        say("parity", f"megascan {label} through the executor: one launch "
                      f"== {corpus.n_shards} per-shard launches bit for "
                      f"bit ({spec.stats})")
    assert_clean(stack)


# ----------------------------------------------------------------------
# f. live ingest
# ----------------------------------------------------------------------
def ingest(s: Sizes, stack, rng) -> None:
    from repro.core.queries import BatchQuery

    vocab = stack.corpus.vocab_size
    a, b = (int(x) for x in rng.choice(vocab, 2, replace=False))
    sentinel = BatchQuery.count([a, b])
    before = stack.engine.execute([sentinel], 1.0)[0].estimate.value
    docs = []
    for _ in range(s.ingest_docs):
        toks = rng.integers(0, vocab - 1, s.ingest_doc_len).astype(np.int32)
        toks[toks >= a] += 1            # every token but the sentinel's
        p = int(rng.integers(0, s.ingest_doc_len - 1))
        toks[p:p + 2] = (a, b)
        docs.append(toks)
    rec = stack.ingestor.step(docs)
    after = stack.engine.execute([sentinel], 1.0)[0].estimate.value
    exact = stack.corpus.count_phrase([a, b])
    if stack.ingestor.errors:
        raise AssertionError(f"ingest errors: {stack.ingestor.errors}")
    if not (after == exact == before + s.ingest_docs):
        raise AssertionError(f"sentinel count {before} -> {after} (exact "
                             f"{exact}) after {s.ingest_docs} docs")
    assert_clean(stack)
    say("parity", f"ingest: {rec['appended']} docs appended, sentinel "
                  f"count {before:.0f} -> {after:.0f} (exact {exact})")


# ----------------------------------------------------------------------
def run(s: Sizes, seed: int) -> None:
    """Phases b-f at sizes ``s``; raises on any failure."""
    from repro.launch.serve_stack import ServeConfig, build_serving_stack

    compiles = Compiles()
    rng = np.random.default_rng(seed)
    with compiles.phase("b. build", fatal=True):
        corpus, model, pcfg, index = build(s, seed)
    with compiles.phase("c. kernel parity"):
        kernel_parity(s, corpus, model, index, rng)
    stack = build_serving_stack(corpus, index, ServeConfig(
        window=True, rate=0.1, max_batch=32, workers=4, seed=seed,
        ingest=True, ingest_model=model, ingest_pv_cfg=pcfg,
        ingest_infer_steps=s.ingest_infer_steps, ingest_yield_s=0.0))
    with stack:
        queries = mixed_queries(s, corpus, rng)
        with compiles.phase("d. serve"):
            serve(s, stack, corpus, index, queries)
        with compiles.phase("e. megascan via executor"):
            megascan_executor(s, stack, corpus, index, rng)
        with compiles.phase("f. live ingest"):
            ingest(s, stack, rng)
    if FAILED:
        raise AssertionError(f"failed: {FAILED}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        from repro.utils.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        import jax
        devices = jax.devices()
        dev = devices[0]
        if dev.platform != "tpu":
            say("error", f"JAX found no TPU (platform {dev.platform!r}); "
                         f"this smoke runs only on the chip")
            return 2
        say("figure", f"device {dev.device_kind} x{len(devices)}; "
                      f"compile cache {cache}")
        t0 = time.perf_counter()
        run(Sizes(), args.seed)
        stats = dev.memory_stats() or {}
        say("timing", f"total {time.perf_counter() - t0:.3f} s; peak device "
                      f"memory {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}"
                      f" GiB")
        entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        say("figure", f"compile cache {cache}: {entries} entries")
    except Exception:  # noqa: BLE001 - any failure fails the smoke
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
