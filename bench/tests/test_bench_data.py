"""The data generator gives the stated shapes, laws and nonzero counts."""
import numpy as np
import pytest

from bench.data import Csr, generator, tpch

TPCH = {"universe": 8192, "parts": 6000, "rows_per_day": 400, "zipf": 2.0}
SEED = 2**40 + 17


def csr_of_dense(dense):
    r, k = np.nonzero(dense)
    indptr = np.concatenate([[0], np.cumsum(np.bincount(
        r, minlength=dense.shape[0]))])
    return Csr(indptr, k, dense[r, k])


@pytest.mark.parametrize("b", [0, 1])
def test_tpch_block(b):
    blk = tpch.corpus_block(TPCH, SEED, b, 6)
    assert blk.dense.shape == (6, 8192) and blk.dense.dtype == np.float32
    assert np.all(blk.dense.sum(axis=1) == 400)
    assert blk.keys.max() < 6000                 # keys past parts unused
    ref = csr_of_dense(blk.dense)
    assert np.array_equal(ref.indptr, blk.indptr)
    assert np.array_equal(ref.keys, blk.keys)
    assert np.array_equal(ref.vals, blk.vals)
    # Zipf z=2: the most popular key takes about 1/zeta(2) of the draws
    top = blk.dense.max(axis=1) / 400
    assert np.all((top > 0.5) & (top < 0.72))


def test_tpch_seeds():
    a = tpch.corpus_block(TPCH, SEED, 0, 4).dense
    assert np.array_equal(a, tpch.corpus_block(TPCH, SEED, 0, 4).dense)
    assert not np.array_equal(a, tpch.corpus_block(TPCH, SEED, 1, 4).dense)
    assert not np.array_equal(a, tpch.corpus_block(TPCH, SEED + 1, 0, 4).dense)
    # every day shares one popularity order: the same key is the top key
    tops = {int(np.argmax(r)) for r in tpch.corpus_block(TPCH, SEED, 2, 8)
            .dense}
    assert len(tops) == 1


def test_tpch_query_pool():
    blocks = [tpch.corpus_block(TPCH, SEED, b, 6) for b in range(2)]
    corpus = Csr.concat([Csr(b.indptr, b.keys, b.vals) for b in blocks])
    dense = np.vstack([b.dense for b in blocks])
    pool = tpch.query_pool(TPCH, SEED, corpus, 3, 2)
    assert pool.shape == (5, 8192)
    for q in pool[:3]:                           # indexed days, asked again
        assert any(np.array_equal(q, d) for d in dense)
    for q in pool[3:]:                           # fresh days of the same law
        assert q.sum() == 400
        assert not any(np.array_equal(q, d) for d in dense)


def test_csr_rows_and_generator_lookup():
    blk = tpch.corpus_block(TPCH, SEED, 0, 3)
    c = Csr.concat([Csr(blk.indptr, blk.keys, blk.vals)] * 2)
    assert c.rows == 6
    assert np.array_equal(c.dense_row(4, 8192), blk.dense[1])
    assert generator("tpch") is tpch
    with pytest.raises(ValueError):
        generator("../x")
