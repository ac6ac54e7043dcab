"""Plain reference of the Rodinia subset the Vortex paper runs (arXiv
2002.12151, Sec. V-B, Fig. 9): for each kernel, its inputs rebuilt from
the launch's data seed, where its output lies in the machine's data
memory, and what the output must be, in numpy.  It imports nothing of
the program under test; the inputs, the buffer layout and the kernels'
semantics are those the Rodinia port documents:

  data seed -> numpy default_rng -> the kernel's inputs, in the order
  listed; buffers placed one after the other from byte 0x1000, each
  rounded up to 16 bytes, in the order listed.

Integer outputs must be exact.  Float outputs are single-precision sums
and products in an order numpy does not share, so they are held to the
tolerance given per kernel (relative and absolute, as numpy.allclose).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import numpy as np

DATA_BASE = 0x1000


class _Layout:
    def __init__(self):
        self.ptr = DATA_BASE

    def place(self, n_words: int) -> int:
        addr = self.ptr
        self.ptr = (self.ptr + 4 * n_words + 15) & ~15
        return addr


# name -> fn(seed, **kwargs) -> (addr, want, tol); tol None = exact int
Expected = Tuple[int, np.ndarray, Any]


def vecadd(seed, n):
    rng = np.random.default_rng(seed)
    a = rng.integers(-1000, 1000, n).astype(np.int32)
    b = rng.integers(-1000, 1000, n).astype(np.int32)
    lay = _Layout()
    lay.place(n), lay.place(n)
    return lay.place(n), a + b, None


def saxpy(seed, n, repeats=1, alpha=2.5):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32)
    y = rng.standard_normal(n).astype(np.float32)
    lay = _Layout()
    lay.place(n), lay.place(n)
    return lay.place(n), np.float32(alpha) * x + y, 1e-5


def sgemm(seed, m, k, n):
    rng = np.random.default_rng(seed)
    A = (rng.standard_normal((m, k)) * 0.5).astype(np.float32)
    B = (rng.standard_normal((k, n)) * 0.5).astype(np.float32)
    lay = _Layout()
    lay.place(m * k), lay.place(k * n)
    return lay.place(m * n), (A @ B).ravel(), 1e-4


def _graph(rng, n_nodes, avg_deg):
    starts = np.zeros(n_nodes + 1, np.int64)
    adj = []
    for u in range(n_nodes):
        deg = rng.integers(1, 2 * avg_deg)
        adj.extend(rng.integers(0, n_nodes, deg).tolist())
        starts[u + 1] = len(adj)
    return starts, adj


def bfs(seed, n_nodes, avg_deg):
    """Level of every node from node 0 (-1: unreachable)."""
    starts, adj = _graph(np.random.default_rng(seed), n_nodes, avg_deg)
    dist = np.full(n_nodes, -1, np.int32)
    dist[0] = 0
    frontier, level = [0], 0
    while frontier:
        level += 1
        nxt = []
        for u in frontier:
            for v in adj[starts[u]:starts[u + 1]]:
                if dist[v] < 0:
                    dist[v] = level
                    nxt.append(v)
        frontier = nxt
    lay = _Layout()
    lay.place(n_nodes + 1), lay.place(len(adj))
    return lay.place(n_nodes), dist, None


def gaussian(seed, n, kcol=0):
    """One elimination step on column kcol: rows below it lose their
    multiple of row kcol."""
    rng = np.random.default_rng(seed)
    A = (rng.standard_normal((n, n)) + np.eye(n) * n).astype(np.float32)
    f = A[kcol + 1:, kcol] / A[kcol, kcol]
    A[kcol + 1:, kcol:] -= f[:, None] * A[kcol, kcol:][None, :]
    return _Layout().place(n * n), A.ravel(), 2e-4


def nn(seed, n, qx=0.3, qy=-1.1):
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal(n).astype(np.float32)
    ys = rng.standard_normal(n).astype(np.float32)
    lay = _Layout()
    lay.place(n), lay.place(n)
    want = (xs - np.float32(qx)) ** 2 + (ys - np.float32(qy)) ** 2
    return lay.place(n), want, 1e-5


def kmeans(seed, n, k):
    """Index of the nearest centroid of each 2-D point."""
    rng = np.random.default_rng(seed)
    pts = rng.standard_normal((n, 2)).astype(np.float32)
    cent = rng.standard_normal((k, 2)).astype(np.float32)
    d = ((pts[:, None, :] - cent[None]) ** 2).sum(-1)
    lay = _Layout()
    lay.place(2 * n), lay.place(2 * k)
    return lay.place(n), d.argmin(1).astype(np.int32), None


KERNELS: Dict[str, Callable[..., Expected]] = {
    "vecadd": vecadd, "saxpy": saxpy, "sgemm": sgemm, "bfs": bfs,
    "gaussian": gaussian, "nn": nn, "kmeans": kmeans,
}


def control_dmem(name: str, kwargs: Dict[str, Any], seed: int,
                 dmem_words: int) -> np.ndarray:
    """The control: the reference put in the machine's place, its float
    outputs held in bfloat16 (the step below the kernels' float32),
    written where the machine writes them in a data memory of
    `dmem_words` int32 words."""
    import ml_dtypes
    addr, want, tol = KERNELS[name](seed, **kwargs)
    if tol is not None:
        want = want.astype(ml_dtypes.bfloat16).astype(np.float32).view(
            np.int32)
    dmem = np.zeros(dmem_words, np.int32)
    dmem[addr // 4: addr // 4 + want.size] = want
    return dmem


def mismatches(name: str, kwargs: Dict[str, Any], seed: int,
               dmem_words: np.ndarray) -> int:
    """Output words of one launch that differ from the reference (beyond
    the kernel's tolerance, for float outputs); `dmem_words` is the
    machine's data memory after the launch, as int32 words."""
    addr, want, tol = KERNELS[name](seed, **kwargs)
    got = np.asarray(dmem_words[addr // 4: addr // 4 + want.size])
    if tol is None:
        return int(np.sum(got != want))
    got = got.astype(np.int32).view(np.float32)
    return int(np.sum(~np.isclose(got, want, rtol=tol, atol=tol)))
