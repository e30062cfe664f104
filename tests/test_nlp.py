"""NLP suite tests: tokenizers, vocab, Huffman, Word2Vec convergence."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nlp.text import (CollectionSentenceIterator,
                                         DefaultTokenizerFactory,
                                         NGramTokenizerFactory)
from deeplearning4j_tpu.nlp.vocab import (VocabCache, build_huffman,
                                          build_vocab, encode_hs_tables,
                                          unigram_table)
from deeplearning4j_tpu.nlp.word2vec import Word2Vec, Word2VecConfig
from deeplearning4j_tpu.nlp.word_vectors import (load_word_vectors,
                                                 write_word_vectors)

CORPUS = [
    "the cat sat on the mat",
    "the dog sat on the rug",
    "a cat and a dog are friends",
    "the king rules the castle",
    "the queen rules the palace",
    "the cat chased the mouse",
    "the dog chased the ball",
    "a king and a queen wear crowns",
] * 30


def test_tokenizer():
    tok = DefaultTokenizerFactory()
    assert tok("The CAT, sat!") == ["the", "cat", "sat"]
    ng = NGramTokenizerFactory(1, 2)
    toks = ng("a b c")
    assert "a b" in toks and "b c" in toks and "a" in toks


def test_vocab_build_and_trim():
    cache = build_vocab(CORPUS[:8], DefaultTokenizerFactory(),
                        min_word_frequency=2)
    assert "the" in cache and cache.index_of("the") == 0  # most frequent
    assert cache.word_frequency("the") > cache.word_frequency("cat")
    # doc frequency counted once per sentence
    assert cache.doc_frequency("the") == 6


def test_huffman_codes_valid():
    cache = build_vocab(CORPUS, DefaultTokenizerFactory())
    build_huffman(cache)
    V = len(cache)
    # prefix-free: no word's code is a prefix of another's
    codes = {tuple(cache.vocab[w].codes) for w in cache.index}
    assert len(codes) == V
    for w in cache.index:
        vw = cache.vocab[w]
        assert len(vw.codes) == len(vw.points)
        assert all(0 <= p < V - 1 for p in vw.points)
    # frequent words get shorter codes
    assert (len(cache.vocab["the"].codes)
            <= len(cache.vocab["mouse"].codes))
    # dense tables
    codes_t, points_t, lengths = encode_hs_tables(cache)
    assert codes_t.shape == points_t.shape
    assert int(lengths[cache.index_of("the")]) == len(cache.vocab["the"].codes)


def test_unigram_table():
    cache = build_vocab(CORPUS, DefaultTokenizerFactory())
    table = unigram_table(cache, table_size=1000)
    counts = np.bincount(table, minlength=len(cache))
    assert counts[cache.index_of("the")] == counts.max()


@pytest.mark.parametrize("negative,use_hs", [(0, True), (5, False),
                                             (5, True)])
def test_word2vec_trains(negative, use_hs):
    cfg = Word2VecConfig(vector_size=32, window=3, epochs=3,
                         batch_size=512, negative=negative, use_hs=use_hs,
                         seed=7)
    w2v = Word2Vec(CORPUS, cfg)
    wv = w2v.fit()
    assert wv.vectors.shape == (len(w2v.cache), 32)
    assert np.all(np.isfinite(np.asarray(wv.vectors)))


def test_word2vec_semantic_sanity():
    """Words in similar contexts end up closer (Word2VecTests parity:
    the beach->sea style nearest-neighbor check, on a toy corpus)."""
    cfg = Word2VecConfig(vector_size=48, window=3, epochs=30, alpha=0.05,
                         batch_size=128, negative=5, use_hs=True, seed=3)
    wv = Word2Vec(CORPUS, cfg).fit()
    # cat/dog share contexts (sat, chased, pets); king/queen share contexts
    assert wv.similarity("cat", "dog") > wv.similarity("cat", "castle")
    assert wv.similarity("king", "queen") > wv.similarity("king", "mouse")


def test_word_vectors_serialization(tmp_path):
    cfg = Word2VecConfig(vector_size=16, epochs=1, batch_size=256)
    wv = Word2Vec(CORPUS[:40], cfg).fit()
    p = str(tmp_path / "vecs.txt")
    write_word_vectors(wv, p)
    wv2 = load_word_vectors(p)
    assert wv2.vectors.shape == wv.vectors.shape
    w = wv.cache.word_for(0)
    np.testing.assert_allclose(wv.word_vector(w), wv2.word_vector(w),
                               atol=1e-5)
    sims1 = wv.words_nearest("the", 3)
    sims2 = wv2.words_nearest("the", 3)
    assert [w for w, _ in sims1] == [w for w, _ in sims2]


def test_word_vectors_binary_roundtrip(tmp_path):
    import numpy as np
    from deeplearning4j_tpu.nlp.word_vectors import (
        WordVectors, load_word_vectors_binary, write_word_vectors_binary)
    from deeplearning4j_tpu.nlp.vocab import VocabCache
    import jax.numpy as jnp

    import pytest

    cache = VocabCache()
    for w in ["alpha", "beta", "gamma"]:
        cache.add_token(w)
    cache.index = [w for w in cache.vocab]
    for i, w in enumerate(cache.index):
        cache.vocab[w].index = i
    vecs = jnp.asarray(np.random.default_rng(0).normal(
        size=(3, 8)).astype(np.float32))
    wv = WordVectors(cache, vecs)
    p = str(tmp_path / "vecs.bin")
    write_word_vectors_binary(wv, p)
    back = load_word_vectors_binary(p)
    np.testing.assert_allclose(np.asarray(back.vectors),
                               np.asarray(vecs), rtol=1e-6)
    assert back.has_word("gamma")
    assert abs(back.similarity("alpha", "beta")
               - wv.similarity("alpha", "beta")) < 1e-6

    # spaced (n-gram) vocab entries can't survive the C binary layout —
    # the writer must refuse rather than corrupt the stream
    cache2 = VocabCache()
    cache2.add_token("multi word")
    cache2.index = ["multi word"]
    cache2.vocab["multi word"].index = 0
    wv2 = WordVectors(cache2, vecs[:1])
    with pytest.raises(ValueError):
        write_word_vectors_binary(wv2, str(tmp_path / "bad.bin"))


def test_word_vectors_binary_no_trailing_newline(tmp_path):
    """Binaries written WITHOUT the per-record newline (gensim's
    save_word2vec_format layout) must parse identically — the loader skips
    leading separator whitespace instead of consuming a fixed byte."""
    import numpy as np
    from deeplearning4j_tpu.nlp.word_vectors import load_word_vectors_binary

    vecs = np.random.default_rng(1).normal(size=(3, 5)).astype("<f4")
    words = ["alpha", "beta", "gamma"]
    p = tmp_path / "gensim.bin"
    with open(p, "wb") as f:
        f.write(b"3 5\n")
        for w, v in zip(words, vecs):
            f.write(w.encode() + b" " + v.tobytes())  # no trailing '\n'
    back = load_word_vectors_binary(str(p))
    np.testing.assert_allclose(np.asarray(back.vectors), vecs, rtol=1e-6)
    assert back.has_word("beta")


def test_word2vec_negative_requires_syn1neg_on_warm_start():
    """negative>0 with a warm start missing the syn1neg table must fail
    loudly, not silently train against a dummy table."""
    import pytest

    from deeplearning4j_tpu.nlp.word2vec import Word2Vec, Word2VecConfig

    corpus = ["the cat sat on the mat", "the dog sat on the rug"] * 5
    cfg = Word2VecConfig(vector_size=8, negative=5, epochs=1, batch_size=64)
    a = Word2Vec(corpus, cfg)
    a.fit()
    b = Word2Vec(corpus, cfg, cache=a.cache)
    with pytest.raises(ValueError, match="syn1neg"):
        b.fit(initial_weights=(a.syn0, a.syn1, None))


# -- Pallas fused kernel (ops/pallas_word2vec) ------------------------------

def _rand_chunk(B=256, L=7, D=32, V=64, K=3, seed=0):
    rng = np.random.RandomState(seed)
    return dict(
        syn0=jnp.asarray(rng.randn(V, D), jnp.float32) * 0.1,
        syn1=jnp.asarray(rng.randn(V, D), jnp.float32) * 0.1,
        sneg=jnp.asarray(rng.randn(V, D), jnp.float32) * 0.1,
        inputs=jnp.asarray(rng.randint(0, V, B), jnp.int32),
        targets=jnp.asarray(rng.randint(0, V, B), jnp.int32),
        codes=jnp.asarray(rng.randint(0, 2, (B, L)), jnp.float32),
        points=jnp.asarray(rng.randint(0, V, (B, L)), jnp.int32),
        mask=jnp.asarray((rng.rand(B, L) < 0.7).astype(np.float32)),
        negs=jnp.asarray(rng.randint(0, V, (B, K)), jnp.int32),
        pmask=jnp.asarray((rng.rand(B) < 0.9).astype(np.float32)),
        alpha=jnp.float32(0.025), D=D, K=K)


@pytest.mark.parametrize("use_hs,negative", [(True, 0), (False, 3),
                                             (True, 3)])
def test_pallas_fused_kernel_matches_xla(use_hs, negative):
    """The VMEM-resident kernel (interpret mode here) must match the XLA
    updates to bf16 precision — including the combined HS+neg case, where
    both objectives read chunk-start tables and syn0 deltas sum."""
    from deeplearning4j_tpu.nlp.word2vec import _hs_update, _neg_update
    from deeplearning4j_tpu.ops.pallas_word2vec import fused_chunk_update

    c = _rand_chunk()
    D = c["D"]
    a0, a1, an = fused_chunk_update(
        c["syn0"], c["syn1"] if use_hs else jnp.zeros((1, D)),
        c["sneg"] if negative else jnp.zeros((1, D)),
        c["inputs"], c["targets"], c["codes"], c["points"], c["mask"],
        c["negs"], c["pmask"], c["alpha"],
        use_hs=use_hs, negative=negative, block=128, interpret=True)
    r0 = c["syn0"]
    if use_hs:
        h0, r1 = _hs_update(c["syn0"], c["syn1"], c["inputs"], c["codes"],
                            c["points"], c["mask"] * c["pmask"][:, None],
                            c["alpha"])
        r0 = r0 + (h0 - c["syn0"])
        assert float(jnp.max(jnp.abs(a1 - r1))) < 1e-4
    if negative:
        n0, rn = _neg_update(c["syn0"], c["sneg"], c["inputs"],
                             c["targets"], c["negs"], c["pmask"],
                             c["alpha"])
        r0 = r0 + (n0 - c["syn0"])
        assert float(jnp.max(jnp.abs(an - rn))) < 1e-4
    assert float(jnp.max(jnp.abs(a0 - r0))) < 2e-4


def test_word2vec_kernel_config_validation():
    w2v = Word2Vec(CORPUS[:8], Word2VecConfig(kernel="XLA", epochs=1))
    with pytest.raises(ValueError, match="kernel"):
        w2v.fit()


def test_word2vec_pallas_path_converges():
    """kernel='pallas' end-to-end through fit() (interpreter off-TPU):
    same semantic-sanity assertions as the XLA-path test."""
    cfg = Word2VecConfig(vector_size=48, window=3, epochs=30, alpha=0.05,
                         batch_size=128, negative=5, use_hs=True, seed=3,
                         kernel="pallas")
    wv = Word2Vec(CORPUS, cfg).fit()
    assert wv.similarity("cat", "dog") > wv.similarity("cat", "castle")
    assert wv.similarity("king", "queen") > wv.similarity("king", "mouse")


def test_word2vec_pallas_neg_only_fit():
    """use_hs=False + kernel='pallas': no Huffman tables exist; the kernel
    must still compile (dummy (B,1) HS blocks) and train."""
    cfg = Word2VecConfig(vector_size=16, window=3, epochs=2, negative=5,
                         use_hs=False, batch_size=256, kernel="pallas")
    wv = Word2Vec(CORPUS, cfg).fit()
    assert np.all(np.isfinite(np.asarray(wv.vectors)))


def test_build_vocab_distributed_matches_sequential():
    """TextPipeline parity: distributed term/doc counting produces the
    same VocabCache as the sequential build on the same corpus."""
    from deeplearning4j_tpu.nlp.distributed import build_vocab_distributed
    from deeplearning4j_tpu.nlp.vocab import build_vocab

    seq = build_vocab(CORPUS, DefaultTokenizerFactory(),
                      min_word_frequency=2)
    dist = build_vocab_distributed(CORPUS, min_word_frequency=2,
                                   n_workers=3, n_shards=5)
    assert dist.index == seq.index
    assert dist.num_docs == seq.num_docs
    for w in seq.index:
        assert dist.word_frequency(w) == seq.word_frequency(w)
        assert dist.doc_frequency(w) == seq.doc_frequency(w)


def test_word2vec_zero_epochs_trains_nothing():
    """epochs=0 must leave the freshly-initialized tables untouched
    (the streamed epoch-0 path must not dispatch)."""
    cfg = Word2VecConfig(vector_size=16, epochs=0, batch_size=256, seed=1)
    w2v = Word2Vec(CORPUS[:16], cfg)
    w2v.fit()
    # syn1 starts all-zero and only training moves it
    assert not np.asarray(w2v.syn1).any()


def test_word2vec_multi_slab_streaming_and_replay(monkeypatch):
    """Exercise the slab pipeline end to end: multiple uniform slabs,
    the non-resident (host-streamed) regime, and cached replay across
    epochs/fits — results must stay finite and semantically sane."""
    from deeplearning4j_tpu.nlp import word2vec as w2v_mod

    monkeypatch.setattr(w2v_mod, "PAIRS_PER_SLAB", 2048)
    monkeypatch.setattr(w2v_mod, "RESIDENT_PAIR_CAP", 4096)  # slabs 3+ stream
    cfg = Word2VecConfig(vector_size=24, window=3, epochs=3, negative=3,
                         use_hs=True, batch_size=512, seed=5)
    w2v = Word2Vec(CORPUS, cfg)
    wv = w2v.fit()
    assert len(w2v._dev_cache["slabs"]) >= 3     # really multi-slab
    # at least one slab beyond the cap stayed host-side numpy
    assert any(isinstance(slab[0], np.ndarray)
               for slab, _, _ in w2v._dev_cache["slabs"])
    assert np.isfinite(np.asarray(wv.vectors)).all()
    # replayed fit (cached slabs): same seed + same pair schedule must
    # REPRODUCE the run bit-for-bit — streaming is deterministic
    first = np.asarray(wv.vectors).copy()
    wv2 = w2v.fit()
    np.testing.assert_array_equal(np.asarray(wv2.vectors), first)


def test_word2vec_exact_pair_mode():
    """pair_mode='exact' applies the window shrink host-side: the device
    trains only surviving pairs (~(W+1)/2W of candidates), fresh per
    epoch, and convergence quality matches the masked default."""
    from deeplearning4j_tpu.nlp.word2vec import (_corpus_pair_blocks,
                                                 corpus_pairs)

    # pair-count: host shrink keeps ~ (W+1)/(2W) of the candidates
    idx = [np.arange(50, dtype=np.int32) % 7 for _ in range(40)]
    full = corpus_pairs(idx, window=5)[0].size
    rng = np.random.RandomState(0)
    kept = sum(b[0].size for b in _corpus_pair_blocks(idx, 5,
                                                      shrink_rng=rng))
    frac = kept / full
    assert 0.45 < frac < 0.68, frac     # expectation 0.6 at W=5

    base = dict(vector_size=48, window=3, epochs=30, alpha=0.05,
                batch_size=128, negative=5, use_hs=True, seed=3)
    w2v = Word2Vec(CORPUS, Word2VecConfig(**base, pair_mode="exact"))
    wv = w2v.fit()
    assert w2v._dev_cache is None        # no replay cache in exact mode
    assert wv.similarity("cat", "dog") > wv.similarity("cat", "castle")
    assert wv.similarity("king", "queen") > wv.similarity("king", "mouse")
    # refits stream again deterministically
    first = np.asarray(wv.vectors).copy()
    wv2 = w2v.fit()
    np.testing.assert_array_equal(np.asarray(wv2.vectors), first)

    with pytest.raises(ValueError):
        Word2Vec(CORPUS, Word2VecConfig(pair_mode="nope")).fit()


def test_word2vec_exact_mode_with_depth_buckets(monkeypatch):
    """exact mode + depth_buckets>1 drives the bucketed emit/record path
    with slabs=None (per-bucket carry buffers, fresh ragged final slabs
    each epoch)."""
    from deeplearning4j_tpu.nlp import word2vec as w2v_mod

    monkeypatch.setattr(w2v_mod, "PAIRS_PER_SLAB", 2048)   # force multi-slab
    base = dict(vector_size=48, window=3, epochs=30, alpha=0.05,
                batch_size=128, negative=5, use_hs=True, seed=3)
    w2v = Word2Vec(CORPUS, Word2VecConfig(**base, pair_mode="exact",
                                          depth_buckets=2))
    wv = w2v.fit()
    assert w2v._dev_cache is None
    assert np.isfinite(np.asarray(wv.vectors)).all()
    assert wv.similarity("cat", "dog") > wv.similarity("cat", "castle")
    assert wv.similarity("king", "queen") > wv.similarity("king", "mouse")


def test_word2vec_depth_buckets_semantics():
    """depth_buckets>1 slices the HS tables per center-depth bucket —
    exact semantics (masked levels are zeros), so convergence quality
    matches the single-bucket run."""
    base = dict(vector_size=48, window=3, epochs=30, alpha=0.05,
                batch_size=128, negative=5, use_hs=True, seed=3)
    wv1 = Word2Vec(CORPUS, Word2VecConfig(**base)).fit()
    w2 = Word2Vec(CORPUS, Word2VecConfig(**base, depth_buckets=3))
    wv2 = w2.fit()
    # bucketing really happened (regression guard on the boundary math)
    assert len({b for _, _, b in w2._dev_cache["slabs"]}) > 1
    for wv in (wv1, wv2):
        assert wv.similarity("cat", "dog") > wv.similarity("cat", "castle")
        assert wv.similarity("king", "queen") > wv.similarity("king",
                                                              "mouse")
    assert np.isfinite(np.asarray(wv2.vectors)).all()


def test_word2vec_real_corpus_tier():
    """Quality tier over a REAL local text corpus (text8-style plain
    text) — skipped when absent, like the real-MNIST/LFW tiers.  Set
    $TEXT_CORPUS or drop a file at ./data/text8."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.environ.get("TEXT_CORPUS")
    if not path:
        for c in ("data/text8", os.path.join(repo, "data", "text8"),
                  os.path.expanduser("~/.dl4j-tpu/text8")):
            if os.path.isfile(c):
                path = c
                break
    if not path or not os.path.isfile(path):
        pytest.skip("no local text corpus (set TEXT_CORPUS to enable)")

    with open(path) as f:
        text = f.read(2_000_000)            # first ~2 MB
    words = text.split()
    sents = [" ".join(words[i:i + 50]) for i in range(0, len(words), 50)]
    cfg = Word2VecConfig(vector_size=64, window=5, epochs=2, negative=5,
                         use_hs=True, min_word_frequency=5,
                         batch_size=8192, pair_mode="exact")
    wv = Word2Vec(sents, cfg).fit()
    assert len(wv.cache) > 1000
    # frequent function words should have sane neighbors (non-empty,
    # finite similarity structure)
    probe = next((w for w in ("the", "of", "and", "one")
                  if w in wv.cache.vocab), None)
    if probe is None:                       # non-English corpus: fall back
        probe = wv.cache.word_for(0)        # to the most frequent word
    near = wv.words_nearest(probe, 5)
    assert len(near) == 5 and all(np.isfinite(s) for _, s in near)


def test_word2vec_device_pair_mode():
    """pair_mode='device': zero host pair work — the token stream
    uploads once and each epoch is one dispatch that builds pairs,
    masks sentence boundaries and the window shrink, and trains, all
    on device.  Convergence quality matches the masked default, and
    sentence boundaries are respected (no cross-sentence pairs).
    batch_size matches the masked-default quality tests (128): now that
    the device path honors batch_size instead of flooring every chunk
    to 256 positions, the two modes see comparable sequential-update
    granularity — the floor was what collapsed their convergence."""
    base = dict(vector_size=48, window=3, epochs=30, alpha=0.05,
                batch_size=128, negative=5, use_hs=True, seed=3)
    w2v = Word2Vec(CORPUS, Word2VecConfig(**base, pair_mode="device"))
    wv = w2v.fit()
    assert w2v._stream_cache is not None
    assert wv.similarity("cat", "dog") > wv.similarity("cat", "castle")
    assert wv.similarity("king", "queen") > wv.similarity("king", "mouse")
    # refits reuse the uploaded stream and reproduce bit-for-bit
    first = np.asarray(wv.vectors).copy()
    wv2 = w2v.fit()
    np.testing.assert_array_equal(np.asarray(wv2.vectors), first)


def test_word2vec_device_mode_boundary_isolation():
    """Two vocab-disjoint halves of a corpus must not influence each
    other through the device-built pairs: words that never share a
    sentence train only within their half, so each half's co-occurring
    pair is more similar than any cross-half pair."""
    corpus = (["alpha beta alpha beta alpha beta"] * 40
              + ["gamma delta gamma delta gamma delta"] * 40)
    cfg = Word2VecConfig(vector_size=32, window=2, epochs=25, alpha=0.05,
                         batch_size=512, negative=5, use_hs=True, seed=5,
                         pair_mode="device")
    wv = Word2Vec(corpus, cfg).fit()
    assert wv.similarity("alpha", "beta") > wv.similarity("alpha", "delta")
    assert wv.similarity("gamma", "delta") > wv.similarity("gamma", "beta")


def test_word2vec_device_mode_pallas_interpret():
    """The device-built pair path drives the fused kernel (interpreter
    off-TPU) and stays finite/semantically sane.  batch_size 128 for the
    same granularity reason as test_word2vec_device_pair_mode."""
    cfg = Word2VecConfig(vector_size=32, window=3, epochs=10, alpha=0.05,
                         batch_size=128, negative=3, use_hs=True, seed=3,
                         pair_mode="device", kernel="pallas")
    w2v = Word2Vec(CORPUS, cfg)
    wv = w2v.fit()
    assert w2v.kernel_used.name == "pallas-interpret"
    assert np.isfinite(np.asarray(wv.vectors)).all()
    assert wv.similarity("cat", "dog") > wv.similarity("cat", "castle")


def test_word2vec_device_mode_data_parallel():
    """pair_mode='device' + mesh: each device trains a stripe of the
    stream on its own replica, replicas parameter-average per epoch
    (the reference's Spark each-iteration averaging at chip scale).
    Quality matches the single-device run's semantic structure."""
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

    # per-epoch averaging across 8 replicas dilutes the effective step
    # ~n_shards-fold (each replica sees 1/8 of the stream between
    # averages — the reference's averaging trainers have the same
    # property), so train with a proportionally larger alpha + epochs
    mesh = make_mesh(MeshSpec(data=8))
    cfg = Word2VecConfig(vector_size=48, window=3, epochs=60, alpha=0.2,
                         batch_size=256, negative=5, use_hs=True, seed=3,
                         pair_mode="device")
    w2v = Word2Vec(CORPUS, cfg)
    wv = w2v.fit(mesh=mesh)
    assert w2v._stream_cache.get("dp_epoch_fns")  # dp path ran
    assert np.isfinite(np.asarray(wv.vectors)).all()
    assert wv.similarity("cat", "dog") > wv.similarity("cat", "castle")
    assert wv.similarity("king", "queen") > wv.similarity("king", "mouse")
