"""GF(2^8) arithmetic and the systematic Cauchy Reed-Solomon code, in plain
numpy, written from the code's definition:

- the field is GF(2)[x] / (x^8 + x^4 + x^3 + x^2 + 1), polynomial 0x11d;
- RS(n, k) splits an entry into k equal byte slices (the data shards,
  rows 0..k-1) and adds m = n - k parity rows, parity p being the XOR
  over j of C[p, j] * data_j with the Cauchy matrix
  C[p, j] = 1 / ((k + p) XOR j).
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D


def _mul_slow(a: int, b: int) -> int:
    """Shift-and-add multiplication modulo POLY."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= POLY
        b >>= 1
    return out


@functools.cache
def table() -> np.ndarray:
    """u8[256, 256]: the full multiplication table."""
    t = np.zeros((256, 256), np.uint8)
    for a in range(256):
        for b in range(a, 256):
            t[a, b] = t[b, a] = _mul_slow(a, b)
    return t


def inverse(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    row = table()[a]
    return int(np.flatnonzero(row == 1)[0])


def cauchy(n: int, k: int) -> np.ndarray:
    """u8[n - k, k]: C[p, j] = 1 / ((k + p) ^ j)."""
    return np.array(
        [[inverse((k + p) ^ j) for j in range(k)] for p in range(n - k)],
        np.uint8,
    )


def apply(matrix: np.ndarray, shards: np.ndarray) -> np.ndarray:
    """out[i] = XOR_j matrix[i, j] * shards[j], shards u8[j, ...]."""
    t = table()
    out = np.zeros((matrix.shape[0],) + shards.shape[1:], np.uint8)
    for i in range(matrix.shape[0]):
        for j in range(matrix.shape[1]):
            out[i] ^= t[int(matrix[i, j])][shards[j]]
    return out


def encode(entries: np.ndarray, n: int, k: int) -> np.ndarray:
    """u8[N, S] entries -> u8[n, N, S / k]: row r's shard of each entry."""
    e = np.asarray(entries, np.uint8)
    s = e.shape[1] // k
    data = np.stack([e[:, j * s:(j + 1) * s] for j in range(k)])
    return np.concatenate([data, apply(cauchy(n, k), data)])
