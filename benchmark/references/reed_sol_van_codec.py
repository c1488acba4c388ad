"""Plain reference for an erasure codec at the plugin boundary:
jerasure ``reed_sol_van`` with w=8, encode and decode.  GF(2^8) under
the polynomial 0x11d, the systematic Vandermonde distribution matrix
of jerasure's ``reed_sol.c``, shard i = chunk i of every stripe of
``stripe_unit`` bytes; decode by Gaussian elimination on the
generator rows of the first k survivors.

Its own copy of the field and of the matrix: imports nothing of the
program and nothing of ``reed_sol_van.py``.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D


@functools.lru_cache(maxsize=1)
def _tables():
    """(exp[510], log[256]) of the generator 2."""
    exp, log = [0] * 510, [0] * 256
    x = 1
    for i in range(255):
        exp[i], log[x] = x, i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    for i in range(255, 510):
        exp[i] = exp[i - 255]
    return exp, log


def _mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    exp, log = _tables()
    return exp[log[a] + log[b]]


def _inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    exp, log = _tables()
    return exp[255 - log[a]]


@functools.lru_cache(maxsize=256)
def _times(coef: int) -> np.ndarray:
    """(256,) uint8: ``coef`` times every byte."""
    return np.array([_mul(coef, b) for b in range(256)], dtype=np.uint8)


def generator_matrix(k: int, m: int) -> list[list[int]]:
    """All k+m rows of the systematic generator: the identity, then
    the coding rows of ``reed_sol_vandermonde_coding_matrix`` (the
    extended Vandermonde matrix brought to systematic form by column
    operations, the first coding row and the first column of the
    others scaled to ones)."""
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
    return d


def _check_guarantee(guarantee: str) -> None:
    if guarantee not in ("kept", "broken"):
        raise ValueError(f"guarantee is 'kept' or 'broken', not {guarantee!r}")


def _combine(row, shards) -> np.ndarray:
    """Sum over GF(2^8) of ``row[j]`` times ``shards[j]``."""
    acc = np.zeros_like(shards[0])
    for coef, shard in zip(row, shards):
        if coef:
            acc ^= _times(coef)[shard]
    return acc


def encode_shards(
    payload, k: int, m: int, stripe_unit: int, guarantee: str = "kept"
) -> list[np.ndarray]:
    """The k+m shards of ``payload`` (zero-padded to whole stripes) as
    uint8 arrays.  ``guarantee="broken"`` is the control: the last
    coding row leaves the last data chunk out, so the last chunk is no
    longer jerasure's."""
    _check_guarantee(guarantee)
    width = k * stripe_unit
    data = np.frombuffer(payload, dtype=np.uint8)
    pad = -len(data) % width
    if pad:
        data = np.concatenate([data, np.zeros(pad, dtype=np.uint8)])
    chunks = data.reshape(-1, k, stripe_unit)
    shards = [np.ascontiguousarray(chunks[:, i, :]).reshape(-1) for i in range(k)]
    coding = generator_matrix(k, m)[k:]
    if guarantee == "broken":
        coding[-1][-1] = 0
    return shards + [_combine(row, shards) for row in coding]


def _invert(matrix: list[list[int]]) -> list[list[int]]:
    """Inverse of a square matrix over GF(2^8), by Gauss-Jordan
    elimination with row swaps."""
    n = len(matrix)
    a = [list(row) + [int(i == j) for j in range(n)] for i, row in enumerate(matrix)]
    for col in range(n):
        pivot = next((r for r in range(col, n) if a[r][col]), None)
        if pivot is None:
            raise ValueError("the survivors' rows are singular")
        a[col], a[pivot] = a[pivot], a[col]
        inv = _inv(a[col][col])
        a[col] = [_mul(inv, v) for v in a[col]]
        for r in range(n):
            t = a[r][col]
            if r != col and t:
                a[r] = [v ^ _mul(t, p) for v, p in zip(a[r], a[col])]
    return [row[n:] for row in a]


def decode_shards(
    available: dict, k: int, m: int, stripe_unit: int, guarantee: str = "kept"
) -> dict[int, np.ndarray]:
    """All k+m shards from any k or more of them: ``available`` maps a
    position to its shard (equal lengths, whole chunks of
    ``stripe_unit``).  The generator rows of the first k survivors are
    inverted; a lost data shard is its row of the inverse times those
    survivors, a lost coding shard its generator row times the data.
    ``guarantee="broken"`` is the control: every rebuilt shard leaves
    the last of the k survivors out, so a lost chunk no longer comes
    back as it was."""
    _check_guarantee(guarantee)
    have = {
        int(p): np.frombuffer(bytes(s), dtype=np.uint8)
        if isinstance(s, (bytes, bytearray, memoryview))
        else np.ascontiguousarray(s, dtype=np.uint8).reshape(-1)
        for p, s in available.items()
    }
    lengths = {len(s) for s in have.values()}
    if len(lengths) != 1 or next(iter(lengths)) % stripe_unit:
        raise ValueError("shards must be of one length, whole chunks")
    survivors = sorted(have)[:k]
    if len(survivors) < k:
        raise ValueError(f"{len(have)} shards cannot rebuild k={k}")
    gen = generator_matrix(k, m)
    inverse = _invert([gen[p] for p in survivors])
    inputs = [have[p] for p in survivors]

    def rebuilt(row):
        if guarantee == "broken":
            row = list(row[:-1]) + [0]
        return _combine(row, inputs)

    out = dict(have)
    for p in range(k):
        if p not in out:
            out[p] = rebuilt(inverse[p])
    for p in range(k, k + m):
        if p not in out:
            # generator row times the inverse: straight from the survivors
            row = [
                functools.reduce(
                    lambda acc, t: acc ^ t,
                    (_mul(gen[p][j], inverse[j][c]) for j in range(k)),
                )
                for c in range(k)
            ]
            out[p] = rebuilt(row)
    return out
