"""Plain reference for an erasure-coded pool: jerasure ``reed_sol_van``
with w=8 — GF(2^8) under the polynomial 0x11d, the systematic
Vandermonde distribution matrix of jerasure's ``reed_sol.c``, and the
stripe layout of an EC pool (``stripe_unit`` bytes of each of the k
data chunks per stripe, shard i = chunk i of every stripe).

Imports nothing of the program and takes no table from it.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D


@functools.lru_cache(maxsize=1)
def _mul_table() -> np.ndarray:
    """(256, 256) uint8: product of every pair in GF(2^8)."""
    exp = [0] * 510
    log = [0] * 256
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    for i in range(255, 510):
        exp[i] = exp[i - 255]
    tbl = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            tbl[a, b] = exp[log[a] + log[b]]
    return tbl


def _mul(a: int, b: int) -> int:
    return int(_mul_table()[a, b])


def _inv(a: int) -> int:
    row = _mul_table()[a]
    return int(np.nonzero(row == 1)[0][0])


def coding_matrix(k: int, m: int) -> list[list[int]]:
    """The m x k coding rows of ``reed_sol_vandermonde_coding_matrix``:
    the (k+m) x k extended Vandermonde matrix brought to systematic
    form by column operations, first coding row and first column of
    the others scaled to ones."""
    rows, cols = k + m, k
    d = [[0] * cols for _ in range(rows)]
    d[0][0] = 1
    d[rows - 1][cols - 1] = 1
    for i in range(1, rows - 1):
        acc = 1
        for j in range(cols):
            d[i][j] = acc
            acc = _mul(acc, i)
    for i in range(1, cols):
        j = i
        while j < rows and d[j][i] == 0:
            j += 1
        if j >= rows:
            raise ValueError("vandermonde matrix is singular")
        if j != i:
            d[i], d[j] = d[j], d[i]
        if d[i][i] != 1:
            inv = _inv(d[i][i])
            for r in range(rows):
                d[r][i] = _mul(inv, d[r][i])
        for j in range(cols):
            t = d[i][j]
            if j != i and t != 0:
                for r in range(rows):
                    d[r][j] ^= _mul(t, d[r][i])
    for j in range(cols):
        t = d[cols][j]
        if t != 1:
            inv = _inv(t)
            for r in range(cols, rows):
                d[r][j] = _mul(inv, d[r][j])
    for r in range(cols + 1, rows):
        t = d[r][0]
        if t != 1:
            inv = _inv(t)
            for j in range(cols):
                d[r][j] = _mul(d[r][j], inv)
    return [list(row) for row in d[cols:]]


def encode_shards(
    payload: bytes, k: int, m: int, stripe_unit: int, guarantee: str = "kept"
) -> list[np.ndarray]:
    """The k+m shards an EC pool stores for ``payload``, as uint8
    arrays.  ``guarantee="broken"`` is the control: the last coding
    row leaves the last data chunk out, so the object no longer
    survives the loss of any m shards."""
    width = k * stripe_unit
    data = np.frombuffer(payload, dtype=np.uint8)
    pad = -len(data) % width
    if pad:
        data = np.concatenate([data, np.zeros(pad, dtype=np.uint8)])
    chunks = data.reshape(-1, k, stripe_unit)
    shards = [np.ascontiguousarray(chunks[:, i, :]).reshape(-1) for i in range(k)]
    matrix = coding_matrix(k, m)
    if guarantee == "broken":
        matrix[-1][-1] = 0
    elif guarantee != "kept":
        raise ValueError(f"guarantee is 'kept' or 'broken', not {guarantee!r}")
    tbl = _mul_table()
    for row in matrix:
        acc = np.zeros_like(shards[0])
        for coef, shard in zip(row, shards[:k]):
            acc ^= tbl[coef][shard]
        shards.append(acc)
    return shards
