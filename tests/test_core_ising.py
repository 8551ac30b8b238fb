"""Unit + property tests for the Ising/MAX-CUT substrate."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import IsingModel, MaxCutProblem, fig4_example, ising_energy
from repro.core import gset


def brute_force_maxcut(p: MaxCutProblem):
    best = -(10**9)
    for bits in range(2**p.n):
        m = np.array([1 if (bits >> k) & 1 else -1 for k in range(p.n)])
        best = max(best, int(p.cut_value(jnp.asarray(m))))
    return best


def test_fig4_example_structure():
    p = fig4_example()
    assert p.n == 4 and len(p.edges) == 5
    # the paper's partitions: {A}|{BCD} -> 1, {A,B}|{C,D} -> 3
    m_b = jnp.asarray([1, -1, -1, -1])
    m_c = jnp.asarray([1, 1, -1, -1])
    assert int(p.cut_value(m_b)) == 1
    assert int(p.cut_value(m_c)) == 3
    assert brute_force_maxcut(p) == 3 == p.best_known


@given(st.integers(0, 2**10 - 1), st.integers(0, 10**6))
@settings(max_examples=30, deadline=None)
def test_cut_energy_consistency(bits, seed):
    """cut(m) == (w_total - H(m)) / 2 for the Ising embedding (J=-w, h=0)."""
    rng = np.random.default_rng(seed)
    n = 10
    ii, jj = np.triu_indices(n, k=1)
    keep = rng.random(len(ii)) < 0.4
    if keep.sum() == 0:
        keep[0] = True
    edges = np.stack([ii[keep], jj[keep]], axis=1)
    weights = rng.integers(-3, 4, size=len(edges))
    p = MaxCutProblem(n=n, edges=edges, weights=weights, name="rand")
    model = p.to_ising()
    m = np.array([1 if (bits >> k) & 1 else -1 for k in range(n)], dtype=np.int32)
    h, nbr_idx, nbr_w = model.device_arrays()
    H = int(ising_energy(jnp.asarray(m), h, nbr_idx, nbr_w))
    cut = int(p.cut_value(jnp.asarray(m)))
    assert cut == (p.w_total - H) // 2
    assert cut == int(p.cut_from_energy(H))


def test_dense_sparse_field_agreement():
    p = gset.king_graph(36, seed=5)
    model = p.to_ising()
    from repro.core.ising import local_fields_dense, local_fields_sparse

    h, nbr_idx, nbr_w = model.device_arrays()
    J = jnp.asarray(model.dense_J(), jnp.float32)
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.choice([-1, 1], size=(7, 36)).astype(np.int8))
    fs = local_fields_sparse(m.astype(jnp.int32), h, nbr_idx, nbr_w)
    fd = local_fields_dense(m, h, J)
    np.testing.assert_array_equal(np.asarray(fs), np.asarray(fd))


def test_dense_J_roundtrip():
    p = gset.toroidal_grid(36, seed=2)
    model = p.to_ising()
    J = model.dense_J()
    assert np.array_equal(J, J.T)
    assert np.all(np.diag(J) == 0)
    edges, w = model.edge_list()
    assert len(edges) == len(p.edges)
    model2 = IsingModel.from_edges(model.n, edges, w)
    assert np.array_equal(model2.dense_J(), J)


def test_gset_instances_match_table1():
    """Table I: G11/12/13 have 800 vertices / 1600 edges; King1 3200 edges."""
    for name in ("G11", "G12", "G13"):
        p = gset.load(name)
        assert p.n == 800 and len(p.edges) == 1600
        assert set(np.unique(p.weights)) <= {-1, 1}
    k = gset.load("King1")
    assert k.n == 800 and len(k.edges) == 3200
    # 4-regular / 8-regular degree structure
    deg = np.zeros(800, int)
    for i, j in gset.load("G11").edges:
        deg[i] += 1
        deg[j] += 1
    assert np.all(deg == 4)
    deg = np.zeros(800, int)
    for i, j in k.edges:
        deg[i] += 1
        deg[j] += 1
    assert np.all(deg == 8)


def test_gset_parser():
    text = "3 2\n1 2 1\n2 3 -1\n"
    p = gset.parse_gset_text(text, name="G11")
    assert p.n == 3 and len(p.edges) == 2
    assert p.best_known == 564  # table lookup by name
    np.testing.assert_array_equal(p.edges, [[0, 1], [1, 2]])
    np.testing.assert_array_equal(p.weights, [1, -1])


def test_self_loop_rejected():
    with pytest.raises(ValueError):
        IsingModel.from_edges(3, np.array([[0, 0]]), np.array([1]))


def _sequential_fill(n, edges, weights):
    """Per-edge fill: each edge takes the next free slot of i, then of j."""
    rows = [[] for _ in range(n)]
    for (i, j), w in zip(edges.tolist(), weights.tolist()):
        rows[i].append((j, w))
        rows[j].append((i, w))
    d = max([len(r) for r in rows] + [1])
    idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, d))
    wts = np.zeros((n, d), np.int32)
    for v, r in enumerate(rows):
        for s, (j, w) in enumerate(r):
            idx[v, s], wts[v, s] = j, w
    return idx, wts


@pytest.mark.parametrize("n,n_edges", [(50, 400), (2**16, 300),
                                       (2**16 + 3000, 300)],
                         ids=["small", "uint16-limit", "over-uint16"])
def test_from_edges_matches_sequential_fill(n, n_edges):
    """Slot order is the per-edge fill's, with sources sorted as uint16 up
    to 2^16 spins and as int32 above: repeated edges, both orientations,
    endpoints at the top of the range."""
    rng = np.random.default_rng(n)
    e = rng.integers(0, n, (n_edges, 2))
    e = e[e[:, 0] != e[:, 1]]
    e = np.concatenate([e, e[:20], e[:20, ::-1], [[n - 1, 0], [0, n - 1]]])
    w = rng.integers(-3, 4, len(e))
    m = IsingModel.from_edges(n, e, w)
    idx, wts = _sequential_fill(n, e, w)
    assert m.nbr_idx.dtype == m.nbr_w.dtype == np.int32
    np.testing.assert_array_equal(m.nbr_idx, idx)
    np.testing.assert_array_equal(m.nbr_w, wts)


def test_from_edges_rejects_out_of_range_endpoints():
    for bad in ([[0, 3]], [[-1, 1]]):
        with pytest.raises(ValueError, match="endpoints"):
            IsingModel.from_edges(3, np.array(bad), np.array([1]))


def test_from_edges_rejects_nonfinite_weights():
    edges = np.array([[0, 1], [1, 2]])
    with pytest.raises(ValueError, match="finite"):
        IsingModel.from_edges(3, edges, np.array([1.0, np.nan]))
    with pytest.raises(ValueError, match="finite"):
        IsingModel.from_edges(3, edges, np.array([1.0, np.inf]))
    with pytest.raises(ValueError, match="finite"):
        IsingModel.from_edges(3, edges, np.array([1.0, 2.0]),
                              h=np.array([0.0, np.nan, 0.0]))


def test_from_dense_rejects_nonfinite_J():
    J = np.zeros((3, 3))
    J[0, 1] = J[1, 0] = np.nan
    with pytest.raises(ValueError, match="finite"):
        IsingModel.from_dense(J)
