"""Data pipeline: indexation, tokenization (incl. the producer-consumer
pipeline), packed memmap datasets, DP-sharded loading. Hypothesis property
tests cover tokenizer roundtrips and packing invariants."""
import json
import os

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data.indexer import index_jsonl, read_document
from repro.data.packed_dataset import ChunkedLMDataset, PackedDataset, ShardedLoader, synthetic_dataset
from repro.data.tokenize_pipeline import tokenize_file, tokenize_file_serial
from repro.data.tokenizer import BpeTokenizer, ByteTokenizer


@pytest.fixture(scope="module")
def jsonl_file(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("data") / "corpus.jsonl")
    rng = np.random.default_rng(0)
    docs = []
    words = ["the", "quick", "brown", "fox", "jumps", "over", "lazy", "dog",
             "lorem", "ipsum", "dolor", "sit", "amet"]
    for i in range(200):
        n = int(rng.integers(5, 60))
        docs.append(" ".join(rng.choice(words, n)))
    with open(path, "w") as f:
        for d in docs:
            f.write(json.dumps({"text": d}) + "\n")
    return path, docs


def test_indexation_boundaries(jsonl_file):
    path, docs = jsonl_file
    idx = index_jsonl(path)
    assert len(idx) == len(docs)
    # O(1) random access returns the right document
    for i in (0, 17, 199):
        assert read_document(path, idx, i) == docs[i]


def test_indexation_cached(jsonl_file):
    path, _ = jsonl_file
    idx1 = index_jsonl(path)
    assert os.path.exists(path + ".idx.npy")
    idx2 = index_jsonl(path)
    np.testing.assert_array_equal(idx1, idx2)


@given(st.text(max_size=200))
@settings(max_examples=60, deadline=None)
def test_byte_tokenizer_roundtrip(text):
    tok = ByteTokenizer()
    assert tok.decode(tok.encode(text)) == text


@given(st.text(alphabet=st.characters(min_codepoint=32, max_codepoint=300),
               max_size=120))
@settings(max_examples=40, deadline=None)
def test_bpe_roundtrip(text):
    tok = BpeTokenizer.train(["the quick brown fox " * 20, text], n_merges=50)
    assert tok.decode(tok.encode(text)) == text


def test_bpe_compresses():
    corpus = ["the quick brown fox jumps over the lazy dog " * 10] * 5
    tok = BpeTokenizer.train(corpus, n_merges=200)
    byte_len = len(ByteTokenizer().encode(corpus[0]))
    bpe_len = len(tok.encode(corpus[0]))
    assert bpe_len < byte_len * 0.6


def test_pipeline_matches_serial(jsonl_file, tmp_path):
    """Parallel producer-consumer output is byte-identical to serial."""
    path, _ = jsonl_file
    tok = ByteTokenizer()
    a = tokenize_file(path, str(tmp_path / "par"), tok, n_workers=2,
                      batch_docs=17)
    b = tokenize_file_serial(path, str(tmp_path / "ser"), tok)
    assert a["n_docs"] == b["n_docs"]
    assert a["n_tokens"] == b["n_tokens"]
    ta = np.fromfile(a["tokens_path"], dtype=np.uint32)
    tb = np.fromfile(b["tokens_path"], dtype=np.uint32)
    np.testing.assert_array_equal(ta, tb)
    np.testing.assert_array_equal(np.load(a["docidx_path"]), np.load(b["docidx_path"]))


def test_packed_dataset_random_access(jsonl_file, tmp_path):
    path, docs = jsonl_file
    tok = ByteTokenizer()
    info = tokenize_file_serial(path, str(tmp_path / "pk"), tok)
    ds = PackedDataset(str(tmp_path / "pk"))
    assert ds.n_docs == len(docs)
    # document i decodes back to the original text (+EOS)
    got = ds.document(42).tolist()
    assert tok.decode(got[:-1]) == docs[42]
    assert got[-1] == tok.EOS


@given(st.integers(16, 64), st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_chunking_invariants(seq_len, dp_size):
    ds = synthetic_dataset(20000, 97, "/tmp/repro_chunk_prop", seed=3)
    chunked = ChunkedLMDataset(ds, seq_len, seed=0, shuffle=True)
    # every sample has the right shape and labels are inputs shifted by one
    x, y = chunked.sample(5)
    assert x.shape == (seq_len,) and y.shape == (seq_len,)
    np.testing.assert_array_equal(x[1:], y[:-1])
    # global shuffle is a permutation (no sample lost or duplicated)
    assert len(set(chunked.order.tolist())) == chunked.n_samples


def test_sharded_loader_disjoint_deterministic():
    ds = synthetic_dataset(60000, 97, "/tmp/repro_loader", seed=4)
    chunked = ChunkedLMDataset(ds, 32, seed=0)
    g = 8
    ranks = [ShardedLoader(chunked, g, dp_rank=r, dp_size=4) for r in range(4)]
    batches = [next(iter(r.batches(1))) for r in ranks]
    # together the rank-local batches tile the global batch without overlap
    allrows = np.concatenate([b["tokens"] for b in batches])
    assert allrows.shape == (g, 32)
    uniq = {r.tobytes() for r in allrows}
    assert len(uniq) == g
    # deterministic across re-iteration
    again = next(iter(ranks[0].batches(1)))
    np.testing.assert_array_equal(batches[0]["tokens"], again["tokens"])


def test_synthetic_dataset_files_keyed_by_vocab_size_and_seed(tmp_path):
    """Two runs at one prefix never share data generated for the other's
    vocabulary, size or seed (a reused 512-id file would feed a full-vocab
    run; the other way round would gather out of range)."""
    import repro.core.components  # noqa: F401  (populates the registry)
    from repro.config.registry import DEFAULT_REGISTRY as REG

    prefix = str(tmp_path / "nested" / "syn")
    build = lambda **kw: REG.build("dataset", "synthetic", prefix=prefix,  # noqa: E731
                                   seq_len=8, **kw)
    small = build(n_tokens=5000, vocab=16)
    big = build(n_tokens=5000, vocab=100_000)
    assert int(np.max(small.dataset.tokens)) < 16
    assert int(np.max(big.dataset.tokens)) >= 16
    other_seed = build(n_tokens=5000, vocab=16, seed=1)
    assert not np.array_equal(small.dataset.tokens, other_seed.dataset.tokens)
    again = build(n_tokens=5000, vocab=16)
    assert np.array_equal(small.dataset.tokens, again.dataset.tokens)
