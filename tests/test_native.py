"""Native C++ host sampler tests (parity: tests/cpp/test_quiver_cpu.cpp)."""

import numpy as np
import pytest

from quiver_tpu.cpp import native


@pytest.fixture(scope="module")
def csr(request):
    rng = np.random.default_rng(3)
    n = 300
    deg = rng.poisson(6, n).astype(np.int64)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, size=len(src)).astype(np.int64)
    indptr, indices, eid = native.coo_to_csr_native(src, dst, n)
    return indptr, indices, n


def test_native_builds():
    assert native.native_available(), "g++ build of quiver_cpu.so failed"


def test_failed_build_says_what_the_compiler_said(tmp_path, monkeypatch):
    """The numpy fallback is not silent: the compiler's stderr is in the
    warning, once, and ``native_available()`` is then False."""
    bad = tmp_path / "quiver_cpu.cpp"
    bad.write_text("int main() { this is not c++; }\n")
    monkeypatch.setattr(native, "_SRC", bad)
    monkeypatch.setattr(native, "_LIB", tmp_path / "libquiver_cpu.so")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    with pytest.warns(RuntimeWarning, match=r"(?s)build failed.*error"):
        assert not native.native_available()
    assert not native.native_available()     # remembered, not retried


def test_coo_to_csr_native(csr):
    indptr, indices, n = csr
    assert indptr[-1] == len(indices)
    assert (np.diff(indptr) >= 0).all()


def test_cpu_sample_subset(csr):
    indptr, indices, n = csr
    s = native.CPUSampler(indptr, indices)
    seeds = np.arange(n, dtype=np.int64)
    k = 4
    nbrs, mask, counts = s.sample_neighbors(seeds, k)
    deg = np.diff(indptr)
    np.testing.assert_array_equal(counts, np.minimum(deg, k))
    for v in range(n):
        row = set(indices[indptr[v]: indptr[v + 1]].tolist())
        got = nbrs[v][mask[v]].tolist()
        assert set(got) <= row
        assert len(got) == min(deg[v], k)


def test_cpu_reindex_contract(csr):
    indptr, indices, n = csr
    s = native.CPUSampler(indptr, indices)
    seeds = np.array([1, 5, 9, 200], dtype=np.int64)
    nbrs, mask, _ = s.sample_neighbors(seeds, 5)
    n_id, n_mask, num, local = s.reindex(seeds, nbrs, mask)
    np.testing.assert_array_equal(n_id[:4], seeds)
    valid = n_id[n_mask]
    assert len(set(valid.tolist())) == len(valid) == num
    for b in range(4):
        for j in range(5):
            if mask[b, j]:
                assert n_id[local[b, j]] == nbrs[b, j]
    # non-seed remainder is ascending (matches TPU reindex contract)
    rest = n_id[4:num]
    assert (np.diff(rest) > 0).all()


def test_cpu_multihop(csr):
    indptr, indices, n = csr
    s = native.CPUSampler(indptr, indices)
    seeds = np.arange(8, dtype=np.int64)
    n_id, n_mask, num, blocks = s.sample_multihop(seeds, [4, 3])
    assert len(blocks) == 2
    assert blocks[-1][2] == 8  # innermost targets = seeds
    assert num == n_mask.sum()


def test_neighbour_num(csr):
    indptr, indices, n = csr
    out = native.neighbour_num_native(indptr, indices, [3, 2])
    assert out.shape == (n,)
    deg = np.diff(indptr)
    # zero-degree nodes expand to nothing
    assert (out[deg == 0] == 0).all()
    assert (out >= 0).all()
