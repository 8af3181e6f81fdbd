"""Per-kernel allclose vs pure-jnp oracles: shape sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip without the dev extra
    from _hypothesis_compat import given, settings, st

from repro.kernels.bitonic_sort import ops as sort_ops
from repro.kernels.bitonic_sort import ref as sort_ref
from repro.kernels.pair_expand import ops as pe_ops
from repro.kernels.pair_expand import ref as pe_ref
from repro.kernels.segment_reduce import ops as seg_ops
from repro.kernels.segment_reduce import ref as seg_ref


# ---------------------------------------------------------------- bitonic --
@pytest.mark.parametrize("n", [2, 7, 16, 100, 255, 256, 1000, 4096])
def test_bitonic_sort_shapes(n):
    rng = np.random.RandomState(n)
    keys = rng.randint(0, max(2, n // 2), size=n).astype(np.int32)  # dup keys
    vals = np.arange(n, dtype=np.int32)
    sk, sv = sort_ops.sort_pairs(jnp.asarray(keys), jnp.asarray(vals))
    rk, rv = sort_ref.sort_pairs(jnp.asarray(keys), jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(rk))
    # bitonic is unstable: compare (key,val) multisets, not order
    got = sorted(zip(np.asarray(sk).tolist(), np.asarray(sv).tolist()))
    want = sorted(zip(keys.tolist(), vals.tolist()))
    assert got == want


def test_bitonic_argsort_is_permutation():
    keys = jnp.asarray(np.random.RandomState(0).randint(-50, 50, 513), jnp.int32)
    order = sort_ops.argsort_i32(keys)
    assert sorted(np.asarray(order).tolist()) == list(range(513))
    np.testing.assert_array_equal(
        np.asarray(keys[order]), np.sort(np.asarray(keys))
    )


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(-(2**20), 2**20), min_size=1, max_size=300))
def test_bitonic_hypothesis(xs):
    keys = jnp.asarray(np.array(xs, np.int32))
    sk, _ = sort_ops.sort_pairs(keys, jnp.zeros_like(keys))
    np.testing.assert_array_equal(np.asarray(sk), np.sort(np.array(xs, np.int32)))


# ------------------------------------------------------------ pair expand --
@pytest.mark.parametrize("n_left,capacity", [(1, 1024), (5, 1024), (700, 2048),
                                             (1024, 4096)])
def test_pair_expand_shapes(n_left, capacity):
    rng = np.random.RandomState(n_left)
    counts = rng.randint(0, 5, size=n_left).astype(np.int32)
    prefix = np.cumsum(counts).astype(np.int32)
    ki, ko, kv = pe_ops.pair_expand(jnp.asarray(prefix), jnp.asarray(counts),
                                    capacity)
    ri, ro, rv = pe_ref.pair_expand(jnp.asarray(prefix), jnp.asarray(counts),
                                    capacity)
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(rv))
    valid = np.asarray(rv)
    np.testing.assert_array_equal(np.asarray(ki)[valid], np.asarray(ri)[valid])
    np.testing.assert_array_equal(np.asarray(ko)[valid], np.asarray(ro)[valid])


def test_pair_expand_enumerates_all_pairs():
    counts = jnp.asarray([2, 0, 3, 1], jnp.int32)
    prefix = jnp.cumsum(counts)
    i, off, valid = pe_ops.pair_expand(prefix, counts, 1024)
    pairs = {(int(a), int(b)) for a, b, v in
             zip(np.asarray(i), np.asarray(off), np.asarray(valid)) if v}
    assert pairs == {(0, 0), (0, 1), (2, 0), (2, 1), (2, 2), (3, 0)}


# ---------------------------------------------------------- segment reduce --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,s", [(10, 8, 4), (512, 128, 16), (1000, 64, 33)])
def test_segment_sum_shapes(n, d, s, dtype):
    rng = np.random.RandomState(n + d)
    ids = np.sort(rng.randint(0, s, size=n)).astype(np.int32)
    data = rng.randn(n, d).astype(np.float32)
    got = seg_ops.sorted_segment_sum(jnp.asarray(data, dtype), jnp.asarray(ids), s)
    # Oracle in fp32: the kernel accumulates in fp32 on the MXU, the bf16 ref
    # does not, so both are compared against fp32 ground truth (taxonomy §E).
    want = seg_ref.sorted_segment_sum(jnp.asarray(data), jnp.asarray(ids), s)
    rtol, atol = (1e-6, 1e-5) if dtype == jnp.float32 else (5e-2, 0.3)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=rtol, atol=atol)


def test_segment_sum_empty_segments_are_zero():
    data = jnp.ones((4, 3), jnp.float32)
    ids = jnp.asarray([0, 0, 3, 3], jnp.int32)
    out = seg_ops.sorted_segment_sum(data, ids, 5)
    np.testing.assert_allclose(np.asarray(out)[1], 0.0)
    np.testing.assert_allclose(np.asarray(out)[4], 0.0)
    np.testing.assert_allclose(np.asarray(out)[0], 2.0)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 200), st.integers(1, 17), st.integers(1, 40))
def test_segment_sum_hypothesis(n, d, s):
    rng = np.random.RandomState(n * d + s)
    ids = np.sort(rng.randint(0, s, size=n)).astype(np.int32)
    data = rng.randn(n, d).astype(np.float32)
    got = seg_ops.sorted_segment_sum(jnp.asarray(data), jnp.asarray(ids), s)
    want = seg_ref.sorted_segment_sum(jnp.asarray(data), jnp.asarray(ids), s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)


# ------------------------------------------------- kernel-backed full join --
def test_mr_join_with_kernel_expansion_matches_jnp():
    from repro.core import mr_join as mj
    from repro.core.relation import Relation

    rng = np.random.RandomState(7)
    l_rows = rng.randint(0, 9, size=(40, 2)).astype(np.int32)
    r_rows = rng.randint(0, 9, size=(37, 2)).astype(np.int32)
    left = Relation.from_numpy(("?k", "?a"), l_rows)
    right = Relation.from_numpy(("?k", "?b"), r_rows)
    out_j, tot_j, _ = mj.mr_join(left, right, 2048, use_kernel=False)
    out_k, tot_k, _ = mj.mr_join(left, right, 2048, use_kernel=True)
    assert int(tot_j) == int(tot_k)
    assert out_j.to_set() == out_k.to_set()


# ----------------------------------------------------------- spmm join ----
from repro.kernels.spmm_join import ops as spmm_ops  # noqa: E402
from repro.kernels.spmm_join import ref as spmm_ref  # noqa: E402


def _layout_oracle(lk: np.ndarray, rk: np.ndarray):
    eq = lk[:, None] == rk[None, :]
    counts = eq.sum(1).astype(np.int32)
    first = (rk[None, :] < lk[:, None]).sum(1).astype(np.int32)
    b = (eq * (np.cumsum(eq, axis=0) - eq)).sum(1).astype(np.int32)
    cl = eq.sum(0).astype(np.int32)
    return counts, first, b, cl


@pytest.mark.parametrize("n_l,n_r", [(1, 1), (2, 3), (40, 7), (130, 70),
                                     (700, 80), (1024, 256), (1100, 300)])
def test_match_layout_shapes(n_l, n_r):
    rng = np.random.RandomState(n_l + n_r)
    lk = rng.randint(0, 11, size=n_l).astype(np.int32)
    rk = rng.randint(0, 11, size=n_r).astype(np.int32)
    want = _layout_oracle(lk, rk)
    for use_kernel in (False, True):
        got = spmm_ops.match_layout(jnp.asarray(lk), jnp.asarray(rk),
                                    use_kernel=use_kernel)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)


def test_match_layout_blocked_ref_matches_one_shot():
    # force the blocked fori_loop path (n_l * n_r above the one-shot cap)
    rng = np.random.RandomState(3)
    n_l = spmm_ref.ONE_SHOT_ELEMS // 64 + 200  # not a BLOCK_ROWS multiple
    lk = rng.randint(0, 13, size=n_l).astype(np.int32)
    rk = rng.randint(0, 13, size=64).astype(np.int32)
    got = spmm_ref.match_layout(jnp.asarray(lk), jnp.asarray(rk))
    want = _layout_oracle(lk, rk)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


@pytest.mark.parametrize("n", [1, 2, 17, 255, 256, 1000, 1024, 1300])
def test_sort_ranks_is_stable_sorted_position(n):
    rng = np.random.RandomState(n)
    keys = rng.randint(0, max(2, n // 3), size=n).astype(np.int32)
    order = np.argsort(keys, kind="stable")
    want = np.empty(n, np.int64)
    want[order] = np.arange(n)
    for use_kernel in (False, True):
        pos = spmm_ops.sort_ranks(jnp.asarray(keys), use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(pos), want)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 9), min_size=1, max_size=120),
       st.lists(st.integers(0, 9), min_size=1, max_size=120))
def test_match_layout_hypothesis(ls, rs):
    lk = np.array(ls, np.int32)
    rk = np.array(rs, np.int32)
    got = spmm_ops.match_layout(jnp.asarray(lk), jnp.asarray(rk),
                                use_kernel=True)
    for g, w in zip(got, _layout_oracle(lk, rk)):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_match_layout_vmaps():
    rng = np.random.RandomState(5)
    lks = rng.randint(0, 6, size=(4, 33)).astype(np.int32)
    rks = rng.randint(0, 6, size=(4, 21)).astype(np.int32)
    fn = jax.vmap(lambda a, b: spmm_ops.match_layout(a, b, use_kernel=False))
    counts, first, b, cl = fn(jnp.asarray(lks), jnp.asarray(rks))
    for i in range(4):
        want = _layout_oracle(lks[i], rks[i])
        for g, w in zip((counts[i], first[i], b[i], cl[i]), want):
            np.testing.assert_array_equal(np.asarray(g), w)


def _join_rows(rel):
    return np.asarray(rel.cols)[np.asarray(rel.valid)]


@pytest.mark.parametrize("capacity", [1, 3, 16, 64, 4096])
def test_matrix_join_matches_mr_join_exactly(capacity):
    """Bit-identical output (order included) at every capacity, including
    overflowing ones — the regrow loop depends on exact truncation."""
    from repro.core import matrix_join as mxj
    from repro.core import mr_join as mj
    from repro.core.relation import Relation

    rng = np.random.RandomState(11)
    left = Relation.from_numpy(
        ("?k", "?a"), rng.randint(0, 5, size=(50, 2)).astype(np.int32))
    right = Relation.from_numpy(
        ("?k", "?b"), rng.randint(0, 5, size=(41, 2)).astype(np.int32))
    out_m, tot_m, ovf_m = mj.mr_join(left, right, capacity)
    out_x, tot_x, ovf_x = mxj.matrix_join(left, right, capacity)
    assert int(tot_m) == int(tot_x)
    assert bool(ovf_m) == bool(ovf_x)
    np.testing.assert_array_equal(_join_rows(out_m), _join_rows(out_x))


def test_matrix_left_join_matches_mr_left_join():
    from repro.core import matrix_join as mxj
    from repro.core import mr_join as mj
    from repro.core.relation import Relation

    rng = np.random.RandomState(13)
    left = Relation.from_numpy(
        ("?k", "?a"), rng.randint(0, 9, size=(40, 2)).astype(np.int32))
    right = Relation.from_numpy(
        ("?k", "?b"), rng.randint(0, 9, size=(30, 2)).astype(np.int32))
    out_m, tot_m, _ = mj.left_join(left, right, 512)
    out_x, tot_x, _ = mxj.matrix_left_join(left, right, 512)
    assert int(tot_m) == int(tot_x)
    assert out_m.to_set() == out_x.to_set()


def test_matrix_join_kernel_path_matches_ref_path():
    from repro.core import matrix_join as mxj
    from repro.core.relation import Relation

    rng = np.random.RandomState(17)
    left = Relation.from_numpy(
        ("?k", "?a"), rng.randint(0, 7, size=(60, 2)).astype(np.int32))
    right = Relation.from_numpy(
        ("?k", "?b"), rng.randint(0, 7, size=(44, 2)).astype(np.int32))
    out_r, tot_r, _ = mxj.matrix_join(left, right, 1024, use_kernel=False)
    out_k, tot_k, _ = mxj.matrix_join(left, right, 1024, use_kernel=True)
    assert int(tot_r) == int(tot_k)
    np.testing.assert_array_equal(_join_rows(out_r), _join_rows(out_k))
