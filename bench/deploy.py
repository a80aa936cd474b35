"""Set-up of one deployment: its corpus, the program's index build and
serving stack, the warm-up, and the tables the reference needs.

Only this module and ``drive.py`` call into the program.  The build is
the one ``chip_smoke.py`` proved on the chip: PV-DBOW trained with the
negsamp kernel, k-means allocation with the kmeans kernel (where the
configuration asks for it), the doc-granular asym 256-bit index with
its planning kernels on, and CSR postings built for every shard.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Any, Dict, List

import numpy as np

from bench.gen import corpus as gen_corpus


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def sub_seeds(seed: int) -> Dict[str, int]:
    """Seeds of each random part of a run, derived from ``--seed``; each
    fits the 31 bits that every consumer accepts."""
    names = ("corpus", "pv_dbow", "lsh", "kmeans", "serve", "traffic")
    state = np.random.SeedSequence(int(seed)).generate_state(len(names))
    return {n: int(s) & 0x7FFFFFFF for n, s in zip(names, state)}


@dataclasses.dataclass
class Deployment:
    corpus: Any                 # bench.gen.corpus.Corpus (reference data)
    stack: Any                  # the program's ServingStack
    shard_docs: List[np.ndarray]
    tables: Dict[str, Any]      # what the reference reads of the index
    phases: Dict[str, float]

    def close(self) -> None:
        self.stack.close()


def to_program(q: tuple):
    """A ``bench.gen.queries`` tuple as the program's ``BatchQuery``."""
    from repro.core.queries import BatchQuery
    from repro.core.queries.retrieval import BoolExpr
    if q[0] == "count":
        return BatchQuery.count(q[1])
    if q[0] == "ranked":
        return BatchQuery.ranked(q[1], k=q[2])
    form, *w = q[1]
    x = [BoolExpr.w(int(v)) for v in w]
    return BatchQuery.boolean(x[0] & x[1] if form == "and"
                              else (x[0] | x[1]) & x[2])


def build(cfg: Dict[str, Any], seeds: Dict[str, int],
          serve_seed: int) -> Deployment:
    """The deployment ``cfg`` describes, its random parts drawn from
    ``seeds`` (``sub_seeds``), its sampler seeded with ``serve_seed``."""
    from repro.core.allocation import KMeansConfig, allocate_corpus
    from repro.core.index import build_index
    from repro.core.lsh import LSHConfig
    from repro.core.pv_dbow import PVDBOWConfig, train_pv_dbow
    from repro.data.store import Document, ShardedCorpus, shard_postings
    from repro.launch.serve_stack import ServeConfig, build_serving_stack
    from repro.runtime.controller import ControllerConfig

    phases: Dict[str, float] = {}
    t = time.perf_counter()

    def phase(name: str) -> None:
        nonlocal t
        now = time.perf_counter()
        phases[name] = now - t
        say(f"set-up {name} {now - t:.3f} s")
        t = now

    ref = gen_corpus.generate(cfg["corpus"], seeds["corpus"])
    phase("corpus")
    off = ref.offsets
    docs = [Document(i, ref.tokens[off[i]:off[i + 1]])
            for i in range(ref.n_docs)]
    corpus = ShardedCorpus.from_documents(docs, ref.vocab_size,
                                          shard_tokens=cfg["shard_tokens"])
    del docs
    phase("shards")
    pv = cfg["pv_dbow"]
    pcfg = PVDBOWConfig(dim=pv["dim"], temperature=pv["temperature"],
                        steps=pv["steps"], batch_pairs=pv["batch_pairs"],
                        negatives=pv["negatives"], seed=seeds["pv_dbow"],
                        use_kernel=True)
    model = train_pv_dbow(corpus, pcfg)
    model.doc_vecs.block_until_ready()
    phase("pv_dbow")
    lsh = LSHConfig(bits=cfg["bits"], seed=seeds["lsh"])
    if cfg["allocation"] == "kmeans":
        pre = build_index(corpus, model, lsh, use_lsh=False,
                          temperature=pv["temperature"])
        corpus = allocate_corpus(corpus, pre.doc_vecs, cfg=KMeansConfig(
            n_clusters=corpus.n_shards, seed=seeds["kmeans"],
            use_kernel=True))
        del pre
        phase("kmeans")
    elif cfg["allocation"] != "arrival":
        raise ValueError(f"unknown allocation {cfg['allocation']!r}")
    index = build_index(corpus, model, lsh, temperature=pv["temperature"])
    index = dataclasses.replace(index, granularity="doc",
                                use_kernel=True).attach_corpus(corpus)
    phase("index")
    for sh in corpus.shards:
        shard_postings(sh)
    phase("postings")
    sv = cfg["serve"]
    stack = build_serving_stack(corpus, index, ServeConfig(
        window=True, adaptive=True, rate=sv["sampling_rate"],
        max_batch=sv["max_batch"], workers=sv["workers"],
        seed=serve_seed,
        controller_config=ControllerConfig(max_batch=sv["max_batch"])))
    # the reference rebuilds the index from the trained vectors and the
    # LSH seed; the program's signatures are only checked against it
    tables = dict(word_vecs=np.array(model.word_vecs),
                  doc_vecs=np.array(model.doc_vecs),
                  lsh_seed=seeds["lsh"], bits=cfg["bits"],
                  temperature=pv["temperature"],
                  doc_sig=np.array(index.doc_sig),
                  shard_sig=np.array(index.shard_sig))
    del model
    shard_docs = [np.array(sh.doc_ids) for sh in corpus.shards]
    say(f"deployment: {corpus.n_docs} docs, {corpus.n_tokens} tokens, "
        f"{corpus.n_shards} shards")
    return Deployment(ref, stack, shard_docs, tables, phases)


def warm_up(dep: Deployment, warm_queries: List[tuple],
            max_words: int) -> None:
    """Compile every shape the window can use: planning batches of 1 to
    ``max_batch`` queries and Boolean word sets of 1 to ``max_words``
    words (the ops wrappers normalize each unpadded size eagerly), then
    serve ``warm_queries`` through the window so that the controller
    has a cost model and every host path has run once."""
    stack = dep.stack
    index = stack.engine.index
    mb = stack.config.max_batch
    counts = np.bincount(dep.corpus.tokens, minlength=dep.corpus.vocab_size)
    words = [int(w) for w in np.argsort(-counts)[:max(max_words, 1)]]
    for b in range(1, mb + 1):
        index.shard_similarities_batch([[words[i % len(words)]]
                                        for i in range(b)])
    for n in range(1, max_words + 1):
        index.word_shard_similarities_batch(words[:n])
    futs = [stack.window.submit(to_program(q)) for q in warm_queries]
    for f in futs:
        f.result(timeout=600)
