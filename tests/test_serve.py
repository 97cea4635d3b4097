import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import Sketch, priority_sketch
from repro.kernels import bucketize_corpus
from repro.models import init_params
from repro.serve import Engine, Request, SketchIndex


def test_engine_generates():
    cfg = get_config("gemma2-2b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, batch_size=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=4) for i in range(3)]
    done = eng.serve(reqs)
    assert len(done) == 3
    for r in done:
        assert len(r.output) == 4
        assert all(0 <= t < cfg.vocab_size for t in r.output)


def test_engine_greedy_deterministic():
    cfg = get_config("mamba2-370m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    outs = []
    for _ in range(2):
        eng = Engine(cfg, params, batch_size=1, max_len=64)
        r = eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=6)])[0]
        outs.append(tuple(r.output))
    assert outs[0] == outs[1]


def test_sketch_index_topk():
    rng = np.random.default_rng(2)
    n, D = 5000, 30
    idx = SketchIndex(m=256, n_buckets=512)
    vecs = []
    for d in range(D):
        v = np.zeros(n, np.float32)
        ii = rng.choice(n, 400, replace=False)
        v[ii] = rng.uniform(-1, 1, 400)
        vecs.append(v)
        idx.add(f"vec{d}", v)
    q = vecs[7] + 0.05 * rng.standard_normal(n).astype(np.float32) * (vecs[7] != 0)
    top = idx.query(q, top_k=3)
    assert top[0][0] == "vec7"


def _sparse_vecs(rng, D, n=4000, nnz=300):
    vecs = []
    for _ in range(D):
        v = np.zeros(n, np.float32)
        ii = rng.choice(n, nnz, replace=False)
        v[ii] = rng.uniform(-1, 1, nnz)
        vecs.append(v)
    return vecs


def test_sketch_index_incremental_add_matches_rebuild():
    """Appending into the pre-allocated bucketized blocks must equal a
    from-scratch bucketize_corpus of the same sketches — growth events
    (initial_capacity=4, 11 adds -> two doublings) included."""
    rng = np.random.default_rng(3)
    D = 11
    vecs = _sparse_vecs(rng, D)
    idx = SketchIndex(m=128, n_buckets=256, slots=4, initial_capacity=4)
    for d, v in enumerate(vecs):
        idx.add(f"v{d}", v)
    assert idx.capacity == 16  # power-of-two, grown by doubling

    sks = [priority_sketch(jnp.asarray(v), 128, idx.seed) for v in vecs]
    stacked = Sketch(jnp.stack([s.idx for s in sks]),
                     jnp.stack([s.val for s in sks]),
                     jnp.stack([s.tau for s in sks]))
    bc = bucketize_corpus(stacked, n_buckets=256, slots=4)
    np.testing.assert_array_equal(idx._idx[:D], np.asarray(bc.idx))
    np.testing.assert_array_equal(idx._val[:D], np.asarray(bc.val))
    np.testing.assert_allclose(idx._tau[:D], np.asarray(bc.tau), rtol=1e-6)
    np.testing.assert_array_equal(idx._dropped[:D], np.asarray(bc.dropped))


def test_sketch_index_capacity_stable_between_growth():
    """Corpus shape seen by the kernels only changes on doubling — adds in
    between must not re-bucketize or reshape (no recompiles per flush)."""
    rng = np.random.default_rng(4)
    vecs = _sparse_vecs(rng, 7, nnz=200)
    idx = SketchIndex(m=64, n_buckets=128, slots=4, initial_capacity=8)
    shapes = set()
    for d, v in enumerate(vecs):
        idx.add(f"v{d}", v)
        shapes.add(idx._corpus().idx.shape)
    assert shapes == {(8, 128, 4)}
    est = dict(idx.query(vecs[2]))
    assert max(est, key=est.get) == "v2"


def test_sketch_index_all_pairs_consistent_with_queries():
    rng = np.random.default_rng(5)
    vecs = _sparse_vecs(rng, 6)
    idx = SketchIndex(m=128, n_buckets=512, slots=4, initial_capacity=8)
    for d, v in enumerate(vecs):
        idx.add(f"v{d}", v)
    ap = idx.all_pairs()
    assert ap.shape == (6, 6)
    ap_ref = idx.all_pairs(use_pallas=False)
    np.testing.assert_allclose(ap, ap_ref, rtol=1e-4,
                               atol=1e-4 * np.abs(ap_ref).max())


def test_sketch_index_add_many_matches_sequential_add():
    """Batch ingestion (one fused build + vmapped bucketize) must produce
    exactly the blocks sequential adds produce, growth events included."""
    rng = np.random.default_rng(6)
    D = 10
    vecs = _sparse_vecs(rng, D, nnz=250)
    seq = SketchIndex(m=64, n_buckets=128, slots=4, initial_capacity=4)
    for d, v in enumerate(vecs):
        seq.add(f"v{d}", v)
    bat = SketchIndex(m=64, n_buckets=128, slots=4, initial_capacity=4)
    bat.add_many([f"v{d}" for d in range(D)], np.stack(vecs))
    assert len(bat) == len(seq) == D
    assert bat.capacity == seq.capacity
    np.testing.assert_array_equal(bat._idx[:D], seq._idx[:D])
    np.testing.assert_array_equal(bat._val[:D], seq._val[:D])
    np.testing.assert_array_equal(bat._tau[:D], seq._tau[:D])
    np.testing.assert_array_equal(bat._dropped[:D], seq._dropped[:D])
    q = vecs[3]
    np.testing.assert_allclose(dict(bat.query(q))["v3"],
                               dict(seq.query(q))["v3"], rtol=1e-6)


def test_sketch_index_sparse_add_matches_dense_add():
    """(indices, values) ingestion skips the dense materialization but must
    index the identical sketch."""
    rng = np.random.default_rng(7)
    vecs = _sparse_vecs(rng, 3, nnz=150)
    dense = SketchIndex(m=64, n_buckets=128, slots=4)
    sparse = SketchIndex(m=64, n_buckets=128, slots=4)
    for d, v in enumerate(vecs):
        dense.add(f"v{d}", v)
        nz = np.nonzero(v)[0]
        sparse.add(f"v{d}", indices=nz, values=v[nz])
    D = len(vecs)
    np.testing.assert_array_equal(sparse._idx[:D], dense._idx[:D])
    np.testing.assert_array_equal(sparse._val[:D], dense._val[:D])
    np.testing.assert_array_equal(sparse._tau[:D], dense._tau[:D])


def test_sketch_index_add_rejects_ambiguous_input():
    idx = SketchIndex(m=16, n_buckets=64, slots=2)
    v = np.ones(32, np.float32)
    with pytest.raises(ValueError):
        idx.add("both", v, indices=np.arange(3), values=v[:3])
    with pytest.raises(ValueError):
        idx.add("neither")
    with pytest.raises(ValueError):
        idx.add("half", indices=np.arange(3))


def test_sketch_index_rejects_duplicate_names():
    rng = np.random.default_rng(8)
    idx = SketchIndex(m=16, n_buckets=64, slots=2)
    idx.add("a", rng.normal(size=64).astype(np.float32))
    with pytest.raises(ValueError, match="duplicate name 'a'"):
        idx.add("a", rng.normal(size=64).astype(np.float32))
    with pytest.raises(ValueError, match="duplicate"):
        idx.add_many(["b", "a"], rng.normal(size=(2, 64)).astype(np.float32))
    with pytest.raises(ValueError, match="within the batch"):
        idx.add_many(["c", "c"], rng.normal(size=(2, 64)).astype(np.float32))
    assert len(idx) == 1                # failed batches ingested nothing

    from repro.serve import MatrixSketchStore
    st = MatrixSketchStore(16, dim=4)
    st.add("A", rng.normal(size=(32, 4)).astype(np.float32))
    with pytest.raises(ValueError, match="duplicate name 'A'"):
        st.add("A", rng.normal(size=(32, 4)).astype(np.float32))

    from repro.serve import ShardedSketchIndex
    sh = ShardedSketchIndex(num_shards=2, m=16, n_buckets=64, slots=2)
    sh.add("x", rng.normal(size=64).astype(np.float32))
    # the duplicate routes to the *other* shard: only a global check sees it
    with pytest.raises(ValueError, match="duplicate"):
        sh.add("x", rng.normal(size=64).astype(np.float32))


def test_sketch_index_query_error_paths():
    rng = np.random.default_rng(9)
    idx = SketchIndex(m=16, n_buckets=64, slots=2)
    with pytest.raises(ValueError, match="empty index"):
        idx.query(np.ones(64, np.float32))
    idx.add("a", rng.normal(size=64).astype(np.float32))
    with pytest.raises(ValueError, match="coordinates"):
        idx.query(np.ones(32, np.float32))
    with pytest.raises(ValueError, match="1-D"):
        idx.query(np.ones((2, 64), np.float32))

    from repro.serve import MatrixSketchStore, ShardedSketchIndex
    st = MatrixSketchStore(16, dim=4)
    with pytest.raises(ValueError, match="empty store"):
        st.query(np.ones((8, 4), np.float32))
    sh = ShardedSketchIndex(num_shards=2, m=16, n_buckets=64, slots=2)
    with pytest.raises(ValueError, match="empty index"):
        sh.query(np.ones(64, np.float32))


def test_sketch_index_rejects_nonfinite_input():
    rng = np.random.default_rng(10)
    idx = SketchIndex(m=16, n_buckets=64, slots=2)
    v = rng.normal(size=64).astype(np.float32)
    v[5] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        idx.add("bad", v)
    assert len(idx) == 0
    clean = v.copy()
    clean[5] = 0.0
    lax = SketchIndex(m=16, n_buckets=64, slots=2, nonfinite="sanitize")
    lax.add("ok", v)                    # sanitized: NaN -> weight-0 entry
    ref = SketchIndex(m=16, n_buckets=64, slots=2)
    ref.add("ok", clean)
    np.testing.assert_array_equal(lax._idx[:1], ref._idx[:1])
    idx.add("good", clean)
    q = clean.copy()
    q[3] = np.inf
    with pytest.raises(ValueError, match="non-finite"):
        idx.query(q)
    with pytest.raises(ValueError):
        SketchIndex(nonfinite="ignore")


def test_sketch_index_merge_from_mismatch_raises():
    rng = np.random.default_rng(11)
    base = SketchIndex(m=16, n_buckets=64, slots=2, seed=3)
    base.add("a", rng.normal(size=64).astype(np.float32))

    for kw in ({"m": 32}, {"n_buckets": 128}, {"slots": 4}, {"seed": 4}):
        peer = SketchIndex(**{"m": 16, "n_buckets": 64, "slots": 2,
                              "seed": 3, **kw})
        peer.add("a", rng.normal(size=64).astype(np.float32))
        with pytest.raises(ValueError, match="merge"):
            base.merge_from(peer)

    misnamed = SketchIndex(m=16, n_buckets=64, slots=2, seed=3)
    misnamed.add("b", rng.normal(size=64).astype(np.float32))
    with pytest.raises(ValueError, match="names must align"):
        base.merge_from(misnamed)


def _query_vector(kind: str, n: int = 2000) -> np.ndarray:
    """More than m nonzeros (tau finite), at most m (tau = inf), or many
    nonzeros of one magnitude (tied weights)."""
    rng = np.random.default_rng(13)
    v = np.zeros(n, np.float32)
    nnz = {"sampled": 400, "kept_whole": 20, "tied": 400}[kind]
    ii = rng.choice(n, nnz, replace=False)
    v[ii] = (rng.choice([-1.0, 1.0], nnz) if kind == "tied"
             else rng.uniform(-1, 1, nnz))
    return v


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["pallas", "oracle"])
@pytest.mark.parametrize("kind", ["sampled", "kept_whole", "tied"])
def test_sketch_and_query_matches_eager_chain(kind, use_pallas):
    """The served query program equals, bit for bit, the eager chain it
    replaced: ``priority_sketch`` -> ``bucketize`` -> ``query_corpus``."""
    from repro.kernels import bucketize, query_corpus, sketch_and_query
    rng = np.random.default_rng(14)
    # a seed above int32, as the eager hash took it
    idx = SketchIndex(m=64, n_buckets=128, slots=4, seed=2**31 + 7)
    for d, u in enumerate(_sparse_vecs(rng, 6, n=2000, nnz=300)):
        idx.add(f"v{d}", u)
    v = _query_vector(kind)
    sq = priority_sketch(jnp.asarray(v), idx.m, idx.seed)
    assert np.isfinite(float(sq.tau)) == (kind != "kept_whole")
    q_ref = bucketize(sq, n_buckets=idx.n_buckets, slots=idx.slots)
    est_ref = query_corpus(q_ref, idx._corpus(), use_pallas=use_pallas)
    est, q = sketch_and_query(jnp.asarray(v), idx._corpus(),
                              np.uint32(idx.seed),
                              m=idx.m, n_buckets=idx.n_buckets,
                              slots=idx.slots, use_pallas=use_pallas)
    np.testing.assert_array_equal(np.asarray(est), np.asarray(est_ref))
    for got, want in zip(q, q_ref):      # idx, val, tau, dropped
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    served = idx.query(v, use_pallas=use_pallas)
    np.testing.assert_array_equal([e for _, e in served],
                                  np.asarray(est_ref, np.float64)[:6])


def test_sketch_index_bias_aware_query_unchanged():
    """``mode="bias_aware"`` adds the exact-head correction to the same
    estimates as before the query became one program."""
    from repro.kernels import bucketize, query_corpus
    rng = np.random.default_rng(15)
    idx = SketchIndex(m=64, n_buckets=128, slots=4, head_h=8)
    idx.add_many([f"v{d}" for d in range(6)],
                 np.stack(_sparse_vecs(rng, 6, n=2000, nnz=300)))
    v = _query_vector("sampled")
    sq = priority_sketch(jnp.asarray(v), idx.m, idx.seed)
    q = bucketize(sq, n_buckets=idx.n_buckets, slots=idx.slots)
    est = np.asarray(query_corpus(q, idx._corpus()), np.float64)[:6]
    want = est + idx._bias_aware_correction(q, float(sq.tau), v)
    got = idx.query(v, mode="bias_aware")
    assert [n for n, _ in got] == [f"v{d}" for d in range(6)]
    np.testing.assert_array_equal([e for _, e in got], want)
    assert not np.array_equal(want, est)   # the correction did something
