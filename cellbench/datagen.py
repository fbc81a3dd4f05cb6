"""The generators any graph deployment needs (CSR, table, labels, epoch
order), each a function of ``--seed`` alone; a reference's ``make_data``
puts them together with what is its model's own (the weights).

The graph generator is ``quiver_tpu/utils/synthetic.synthetic_csr`` copied
(lognormal degree skew, uniform endpoints), with one change: every seed
gives exactly ``edges`` edges, so that the device tables have the same
shape for every seed and only a checkout's first run compiles.  Nothing
here imports the program.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNKS = 8      # both fixed, so that a seed gives the same data anywhere
PIECE = 1 << 20


def _chunked(n, seed, stream, draw, out):
    """Fill ``out[:n]`` (first axis) chunk by chunk on a few threads, each
    chunk from its own generator ``default_rng([seed, stream, chunk])``:
    numpy's generators release the interpreter lock while they draw."""
    bounds = np.linspace(0, n, CHUNKS + 1).astype(np.int64)

    row = int(np.prod(out.shape[1:])) or 1
    step = max(PIECE // row, 1)

    def one(c):
        # piece by piece, so that what is drawn is copied while it is
        # still in the cache and no piece's pages are touched twice
        rng = np.random.default_rng([seed, stream, c])
        for lo in range(int(bounds[c]), int(bounds[c + 1]), step):
            hi = min(lo + step, int(bounds[c + 1]))
            out[lo:hi] = draw(rng, hi - lo)

    with ThreadPoolExecutor(max_workers=CHUNKS) as pool:
        list(pool.map(one, range(CHUNKS)))
    return out


def csr(nodes, edges, seed):
    """Degree-skewed random CSR with exactly ``edges`` edges:
    ``(indptr int64 [nodes+1], indices int32 [edges])``."""
    rng = np.random.default_rng(seed)
    raw = rng.lognormal(mean=3.0, sigma=1.0, size=nodes)
    deg = np.maximum(raw / raw.sum() * edges, 1).astype(np.int64)
    diff = int(edges - deg.sum())
    if diff > 0:
        deg += np.bincount(rng.integers(0, nodes, diff), minlength=nodes)
    while diff < 0:
        rich = np.flatnonzero(deg > 1)
        take = rich[rng.permutation(len(rich))[:-diff]]
        deg[take] -= 1
        diff += len(take)
    indptr = np.zeros(nodes + 1, dtype=np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = _chunked(
        edges, seed, 0,
        lambda r, n: r.integers(0, nodes, size=n, dtype=np.int32),
        np.empty(edges, dtype=np.int32))
    return indptr, indices


def features(nodes, dim, seed, dtype="float32"):
    """``[nodes, dim]`` rows, the cheapest seeded draw that keeps the
    table's shape and type.  float32: uniform on [-1, 1).  bfloat16: a
    random sign and a random 7-bit mantissa under the exponent of 0.5, so
    uniform on +-[0.5, 1) - two bytes drawn per value, and no float32 copy
    of a table that is stored in bfloat16."""
    if dtype == "bfloat16":
        import ml_dtypes

        def draw(r, n):
            b = r.integers(0, 1 << 16, size=(n, dim), dtype=np.uint16)
            b &= 0x807F
            b |= 0x3F00
            return b

        bits = _chunked(nodes, seed, 1, draw,
                        np.empty((nodes, dim), dtype=np.uint16))
        return bits.view(ml_dtypes.bfloat16)

    def draw(r, n):
        x = r.random((n, dim), dtype=np.float32)
        x *= 2.0
        x -= 1.0
        return x

    return _chunked(nodes, seed, 1, draw,
                    np.empty((nodes, dim), dtype=np.float32))


def labels(nodes, classes, seed):
    return np.random.default_rng(seed + 2).integers(
        0, classes, nodes).astype(np.int32)


def train_order(cfg, seed, epochs):
    """Seed ids in ``SeedLoader`` epoch order: a fixed training set (``train_nodes``
    drawn without replacement) reshuffled every
    epoch, cut into whole batches, the short tail dropped."""
    rng = np.random.default_rng(seed + 4)
    train = rng.choice(cfg["nodes"], cfg["train_nodes"], replace=False)
    b = cfg["batch"]
    per = len(train) // b
    out = np.empty((epochs * per, b), dtype=np.int32)
    for e in range(epochs):
        out[e * per:(e + 1) * per] = rng.permutation(train)[:per * b] \
            .reshape(per, b)
    return out
