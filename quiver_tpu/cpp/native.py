"""ctypes loader for the native host sampler (``csrc/quiver_cpu.cpp``).

Builds the shared library on first use with g++ (no pybind11 in the image);
falls back to a pure-numpy implementation when no compiler is available so
the package never hard-fails.  Parity target: ``CPUQuiver``
(``srcs/cpp/src/quiver/quiver.cpp:11-85``).
"""

from __future__ import annotations

import ctypes
import subprocess
import threading
import warnings
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import numpy as np

_HERE = Path(__file__).resolve().parent
_SRC = _HERE / "csrc" / "quiver_cpu.cpp"
_LIB = _HERE / "libquiver_cpu.so"
_lock = threading.Lock()
_lib = None
_build_failed = False


def _build() -> Optional[ctypes.CDLL]:
    global _build_failed
    with _lock:
        if _LIB.exists() and _LIB.stat().st_mtime >= _SRC.stat().st_mtime:
            return ctypes.CDLL(str(_LIB))
        if _build_failed:
            return None
        cmd = [
            "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
            "-o", str(_LIB), str(_SRC),
        ]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        except (OSError, subprocess.SubprocessError) as e:
            _build_failed = True
            stderr = getattr(e, "stderr", None) or b""
            warnings.warn(
                f"native sampler build failed ({' '.join(cmd)}): {e}\n"
                f"{stderr.decode(errors='replace')[-4000:]}\n"
                "falling back to the numpy host sampler", RuntimeWarning)
            return None
        return ctypes.CDLL(str(_LIB))


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is None and not _build_failed:
        lib = _build()
        if lib is not None:
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C")
            i32p = np.ctypeslib.ndpointer(np.int32, flags="C")
            u8p = np.ctypeslib.ndpointer(np.uint8, flags="C")
            lib.qt_sample.argtypes = [
                i64p, i32p, i32p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32,
                i32p, u8p, i32p,
            ]
            lib.qt_sample.restype = None
            f32p = np.ctypeslib.ndpointer(np.float32, flags="C")
            lib.qt_sample_weighted.argtypes = [
                i64p, i32p, f32p, i32p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32,
                i32p, u8p, i32p,
            ]
            lib.qt_sample_weighted.restype = None
            lib.qt_reindex.argtypes = [
                i32p, ctypes.c_void_p, ctypes.c_int64, i32p, u8p,
                ctypes.c_int32, i32p, u8p, i32p,
            ]
            lib.qt_reindex.restype = ctypes.c_int64
            lib.qt_coo_to_csr.argtypes = [
                i64p, i64p, ctypes.c_int64, ctypes.c_int64, i64p, i32p,
                ctypes.c_void_p,
            ]
            lib.qt_coo_to_csr.restype = None
            lib.qt_neighbour_num.argtypes = [
                i64p, i32p, ctypes.c_int64, i32p, ctypes.c_int32,
                ctypes.c_uint64, ctypes.c_int32, i64p,
            ]
            lib.qt_neighbour_num.restype = None
        _lib = lib
    return _lib


def native_available() -> bool:
    return _get_lib() is not None


def _as_u8_ptr(mask: Optional[np.ndarray]):
    if mask is None:
        return None
    return mask.ctypes.data_as(ctypes.c_void_p)


class CPUSampler:
    """Host-side sampler with the same dense-block contract as the TPU ops."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray,
                 n_threads: int = 0, seed: int = 0x5EED,
                 edge_weights: Optional[np.ndarray] = None):
        self.indptr = np.ascontiguousarray(indptr, dtype=np.int64)
        self.indices = np.ascontiguousarray(indices, dtype=np.int32)
        self.n_threads = n_threads
        self._seed = seed
        self._ctr = 0
        self.cum_weights = None
        if edge_weights is not None:
            from ..ops.sample import row_cumsum_weights

            self.cum_weights = np.ascontiguousarray(
                row_cumsum_weights(self.indptr, edge_weights),
                dtype=np.float32,
            )

    def _next_seed(self) -> int:
        self._ctr += 1
        return (self._seed * 1_000_003 + self._ctr) & (2**64 - 1)

    def sample_neighbors(self, seeds: np.ndarray, k: int,
                         seed_mask: Optional[np.ndarray] = None,
                         seed: Optional[int] = None):
        """``seed`` overrides the internal counter-derived RNG seed so
        callers holding a jax key can make the host tier reproducible
        (``uva.sample_uva``)."""
        seeds = np.ascontiguousarray(seeds, dtype=np.int32)
        B = len(seeds)
        nbrs = np.empty((B, k), dtype=np.int32)
        mask = np.empty((B, k), dtype=np.uint8)
        counts = np.empty(B, dtype=np.int32)
        sm = (
            None if seed_mask is None
            else np.ascontiguousarray(seed_mask, dtype=np.uint8)
        )
        lib = _get_lib()
        rng_seed = seed if seed is not None else self._next_seed()
        if lib is not None and self.cum_weights is not None:
            lib.qt_sample_weighted(
                self.indptr, self.indices, self.cum_weights, seeds,
                _as_u8_ptr(sm), B, k, rng_seed, self.n_threads,
                nbrs.reshape(-1), mask.reshape(-1), counts)
        elif self.cum_weights is not None:  # numpy weighted fallback
            rng = np.random.default_rng(rng_seed % 2**32)
            cw = self.cum_weights
            for b in range(B):
                if sm is not None and not sm[b]:
                    counts[b], mask[b], nbrs[b] = 0, 0, -1
                    continue
                beg, end = self.indptr[seeds[b]], self.indptr[seeds[b] + 1]
                deg = end - beg
                c = int(min(deg, k))
                counts[b] = c
                if deg <= k:
                    nbrs[b, :c] = self.indices[beg:end]
                else:
                    u = rng.random(k).astype(np.float64) * cw[end - 1]
                    pos = np.searchsorted(cw[beg:end], u, side="right")
                    nbrs[b, :k] = self.indices[beg + np.minimum(pos, deg - 1)]
                nbrs[b, c:] = -1
                mask[b] = np.arange(k) < c
            return nbrs, mask.astype(bool), counts
        elif lib is not None:
            lib.qt_sample(self.indptr, self.indices, seeds, _as_u8_ptr(sm),
                          B, k, rng_seed, self.n_threads,
                          nbrs.reshape(-1), mask.reshape(-1), counts)
        else:  # numpy fallback
            rng = np.random.default_rng(rng_seed % 2**32)
            for b in range(B):
                if sm is not None and not sm[b]:
                    counts[b] = 0
                    mask[b] = 0
                    nbrs[b] = -1
                    continue
                beg, end = self.indptr[seeds[b]], self.indptr[seeds[b] + 1]
                row = self.indices[beg:end]
                c = min(len(row), k)
                pick = row[:c] if len(row) <= k else rng.choice(
                    row, size=k, replace=False)
                counts[b] = c
                nbrs[b, :c] = pick[:c]
                nbrs[b, c:] = -1
                mask[b] = np.arange(k) < c
        return nbrs, mask.astype(bool), counts

    def reindex(self, seeds: np.ndarray, nbrs: np.ndarray, mask: np.ndarray,
                seed_mask: Optional[np.ndarray] = None):
        seeds = np.ascontiguousarray(seeds, dtype=np.int32)
        B, k = nbrs.shape
        nbrs = np.ascontiguousarray(nbrs, dtype=np.int32)
        m8 = np.ascontiguousarray(mask, dtype=np.uint8)
        sm = (
            None if seed_mask is None
            else np.ascontiguousarray(seed_mask, dtype=np.uint8)
        )
        n_id = np.zeros(B + B * k, dtype=np.int32)
        n_id_mask = np.zeros(B + B * k, dtype=np.uint8)
        local = np.zeros((B, k), dtype=np.int32)
        lib = _get_lib()
        if lib is not None:
            num = lib.qt_reindex(seeds, _as_u8_ptr(sm), B,
                                 nbrs.reshape(-1), m8.reshape(-1), k,
                                 n_id, n_id_mask, local.reshape(-1))
        else:
            table = {}
            num = 0
            for b in range(B):
                v = sm is None or bool(sm[b])
                n_id[b] = seeds[b] if v else 0
                n_id_mask[b] = v
                if v:
                    table[int(seeds[b])] = b
                    num += 1
            rest = sorted(
                {int(x) for x, mm in zip(nbrs.reshape(-1), m8.reshape(-1))
                 if mm and int(x) not in table}
            )
            for r, x in enumerate(rest):
                n_id[B + r] = x
                n_id_mask[B + r] = 1
                table[x] = B + r
            num += len(rest)
            flat = local.reshape(-1)
            for i, (x, mm) in enumerate(zip(nbrs.reshape(-1), m8.reshape(-1))):
                flat[i] = table[int(x)] if mm else 0
        return n_id, n_id_mask.astype(bool), int(num), local

    def sample_multihop(self, seeds: np.ndarray, sizes: Sequence[int]):
        """Dense multi-hop pipeline mirroring the TPU ``_sample_pipeline``."""
        frontier = np.asarray(seeds, dtype=np.int32)
        fmask = np.ones(len(frontier), dtype=np.uint8)
        blocks: List[Tuple[np.ndarray, np.ndarray, int]] = []
        num_nodes = len(frontier)
        for k in sizes:
            nbrs, mask, _ = self.sample_neighbors(frontier, k, fmask)
            n_id, n_mask, num_nodes, local = self.reindex(
                frontier, nbrs, mask, fmask
            )
            blocks.append((local, mask, int(fmask.sum())))
            frontier, fmask = n_id, n_mask.astype(np.uint8)
        return frontier, fmask.astype(bool), num_nodes, blocks[::-1]


def coo_to_csr_native(src, dst, node_count=None):
    src = np.ascontiguousarray(src, dtype=np.int64)
    dst = np.ascontiguousarray(dst, dtype=np.int64)
    if node_count is None:
        node_count = int(max(src.max(), dst.max())) + 1 if len(src) else 0
    lib = _get_lib()
    if lib is None:
        from ..utils.topology import coo_to_csr
        return coo_to_csr(src, dst, node_count)
    indptr = np.zeros(node_count + 1, dtype=np.int64)
    indices = np.empty(len(src), dtype=np.int32)
    eid = np.empty(len(src), dtype=np.int64)
    lib.qt_coo_to_csr(src, dst, len(src), node_count, indptr, indices,
                      eid.ctypes.data_as(ctypes.c_void_p))
    return indptr, indices, eid


def neighbour_num_native(indptr, indices, sizes, n_threads=0, seed=7):
    indptr = np.ascontiguousarray(indptr, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int32)
    N = len(indptr) - 1
    out = np.zeros(N, dtype=np.int64)
    lib = _get_lib()
    sz = np.ascontiguousarray(sizes, dtype=np.int32)
    if lib is not None:
        lib.qt_neighbour_num(indptr, indices, N, sz, len(sz), seed,
                             n_threads, out)
        return out
    # numpy fallback: expected counts (deterministic upper-fidelity estimate)
    deg = (indptr[1:] - indptr[:-1]).astype(np.float64)
    sampler = CPUSampler(indptr, indices, seed=seed)
    for v in range(N):
        frontier = [v]
        total = 0
        for k in sizes:
            nxt = []
            for u in frontier:
                row = indices[indptr[u]:indptr[u + 1]]
                c = min(len(row), k)
                if len(row) <= k:
                    nxt.extend(row.tolist())
                else:
                    nxt.extend(
                        np.random.default_rng(v).choice(row, k).tolist())
            total += len(nxt)
            frontier = nxt
        out[v] = total
    return out
