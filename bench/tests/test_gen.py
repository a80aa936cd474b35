"""The benchmark's copies of the program's generators draw what the
program's own draw at the same seed."""
import numpy as np

from bench.gen import corpus as gen_corpus
from bench.gen import queries as gen_queries
from bench.reference import Reference, ShardView, phrase_counter

P = dict(n_docs=300, vocab_size=2048, n_topics=24, mean_doc_len=160,
         std_doc_len=60, min_doc_len=16, zipf_exponent=1.07,
         doc_topic_alpha=0.08, topic_locality=0.85)


def _program_corpus(seed):
    from repro.data.corpus import SyntheticCorpusConfig, generate_text_corpus
    from repro.data.store import ShardedCorpus
    docs, _ = generate_text_corpus(SyntheticCorpusConfig(seed=seed, **P))
    return docs, ShardedCorpus.from_documents(docs, P["vocab_size"],
                                              shard_tokens=4096)


def test_corpus_copy_draws_the_programs_corpus():
    docs, _ = _program_corpus(5)
    ours = gen_corpus.generate(P, 5)
    assert ours.n_docs == len(docs)
    for i, d in enumerate(docs):
        assert d.doc_id == i
        np.testing.assert_array_equal(
            ours.tokens[ours.offsets[i]:ours.offsets[i + 1]], d.tokens)


def test_query_pickers_draw_the_programs_queries():
    from benchmarks.common import pick_query_phrases, pick_query_words
    _, corpus = _program_corpus(6)
    ours = gen_corpus.generate(P, 6)
    ref = Reference(ours, [s.doc_ids for s in corpus.shards], {})
    views = [ShardView(ref, s) for s in range(ref.n_shards)]
    count = phrase_counter(ours)
    got = gen_queries.pick_query_phrases(views, 40, np.random.default_rng(1),
                                         count, min_count=3)
    want = pick_query_phrases(corpus, 40, np.random.default_rng(1),
                              min_count=3)
    assert got == want and len(got) == 40
    counts = np.bincount(ours.tokens, minlength=P["vocab_size"])
    np.testing.assert_array_equal(
        gen_queries.pick_query_words(counts, 30, np.random.default_rng(2)),
        pick_query_words(corpus, 30, np.random.default_rng(2)))
    for ph in got[:10] + [[int(ours.tokens[0])], [5, 5, 5]]:
        assert count(ph) == corpus.count_phrase(ph)


def test_open_arrivals_offer_the_same_gaps_in_every_order():
    a, b = (gen_queries.open_arrivals(40.0, 10.0, np.random.default_rng(1),
                                      start)
            for start in (0, 123))
    assert len(a) == len(b) == 400
    assert not np.array_equal(a, b)
    np.testing.assert_allclose(np.roll(np.diff(a, prepend=0.0), -123),
                               np.diff(b, prepend=0.0))
    assert (np.diff(a) >= 0).all() and a[0] >= 0 and a[-1] < 10.0
    assert (np.diff(b) >= 0).all() and b[0] >= 0 and b[-1] < 10.0
