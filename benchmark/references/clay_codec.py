"""Plain reference for the CLAY coupled-layer MSR code (Vajha et al.,
FAST '18; ``src/erasure-code/clay/ErasureCodeClay.cc`` as published)
with ``scalar_mds=jerasure technique=reed_sol_van``: encode, the
sub-chunk reads of a single-chunk repair, and the repair.

The code.  The k + m chunks and nu zero "virtual" chunks (nu pads k + m
to a multiple of q = d - k + 1) are the nodes (x, y) of a q x t grid,
node = y * q + x; parity chunk k + j is node k + nu + j.  A chunk is
q^t sub-chunks, one a *plane* z; digit y of z (most significant first)
is z_y.  Node (x, y) is a *dot* of plane z when z_y = x.  Any other
node is paired with its *companion*: node (z_y, y) in the plane that
has digit y replaced by x.  The coupled values C (what is stored) and
the uncoupled values U of a pair are the four symbols of the 2 + 2
``reed_sol_van`` code, the member with the larger x first:
(C_a, C_b, U_a, U_b) with (U_a, U_b) = G (C_a, C_b); a dot has U = C.
In every plane the U of the q * t nodes are a codeword of
``reed_sol_van`` (k + nu, m).

Departures from the published description, none of which changes a
byte: every step works on sub-chunk z of ALL stripes at once (arrays
of (stripes, sub-chunk bytes)); a node's U is worked out on its own,
of the two symbols at hand, where the C++ fills both members of a pair
in one call; planes are visited by intersection score through a sort
where the C++ walks a map of scores.

Its own copy of the field, the matrix and the inversion: imports
nothing of the program and nothing of the other references.
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


@functools.lru_cache(maxsize=16)
def generator_matrix(k: int, m: int) -> tuple:
    """All k+m rows of jerasure's systematic ``reed_sol_van``
    generator (``reed_sol_vandermonde_coding_matrix``: the extended
    Vandermonde matrix brought to systematic form by column
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
    return tuple(tuple(row) for row in d)


def _invert(matrix) -> list[list[int]]:
    """Inverse of a square matrix over GF(2^8), by Gauss-Jordan
    elimination with row swaps."""
    n = len(matrix)
    a = [list(row) + [int(i == j) for j in range(n)] for i, row in enumerate(matrix)]
    for col in range(n):
        pivot = next((r for r in range(col, n) if a[r][col]), None)
        if pivot is None:
            raise ValueError("the rows are singular")
        a[col], a[pivot] = a[pivot], a[col]
        inv = _inv(a[col][col])
        a[col] = [_mul(inv, v) for v in a[col]]
        for r in range(n):
            t = a[r][col]
            if r != col and t:
                a[r] = [v ^ _mul(t, p) for v, p in zip(a[r], a[col])]
    return [row[n:] for row in a]


@functools.lru_cache(maxsize=4096)
def _solve(rows: tuple, have: tuple, want: int) -> tuple:
    """A code's symbol ``want`` as coefficients over its symbols
    ``have`` (as many as the code's dimension), ``rows`` its generator."""
    inverse = _invert([rows[i] for i in have])
    return tuple(
        functools.reduce(
            lambda acc, t: acc ^ t,
            (_mul(rows[want][j], inverse[j][c]) for j in range(len(have))),
        )
        for c in range(len(have))
    )


def _combine(coefs, arrays):
    """Sum over GF(2^8) of ``coefs[j]`` times ``arrays[j]``.  None
    stands for an array of zeros (a virtual node's), going in and
    coming out."""
    acc = None
    for coef, arr in zip(coefs, arrays):
        if coef and arr is not None:
            term = _times(coef)[arr]
            acc = term if acc is None else acc ^ term
    return acc


# -- the grid -------------------------------------------------------------


def geometry(k: int, m: int, d: int | None = None) -> tuple[int, int, int, int]:
    """(d, q, t, nu) of a profile; d defaults to k + m - 1."""
    d = k + m - 1 if d is None else d
    if not k <= d <= k + m - 1:
        raise ValueError(f"d={d} is not within [{k}, {k + m - 1}]")
    q = d - k + 1
    nu = -(k + m) % q
    return d, q, (k + m + nu) // q, nu


class _Grid:
    def __init__(self, k: int, m: int, d: int | None):
        self.k, self.m = k, m
        self.d, self.q, self.t, self.nu = geometry(k, m, d)
        self.nodes = self.q * self.t
        self.planes = self.q**self.t
        self.virtual = set(range(k, k + self.nu))
        self.mds = generator_matrix(k + self.nu, m)

    def node(self, chunk: int) -> int:
        return chunk if chunk < self.k else chunk + self.nu

    def digit(self, z: int, y: int) -> int:
        return z // self.q ** (self.t - 1 - y) % self.q

    def companion(self, z: int, x: int, y: int) -> int:
        """Plane z with digit y replaced by x."""
        return z + (x - self.digit(z, y)) * self.q ** (self.t - 1 - y)

    def score(self, z: int, nodes) -> int:
        """How many of ``nodes`` are dots of plane z."""
        return sum(1 for n in nodes if n % self.q == self.digit(z, n // self.q))

    def mds_rows(self, unknown) -> tuple[tuple, dict]:
        """The U of the ``unknown`` nodes of a plane from the U of the
        first k + nu others: (those nodes, {unknown node: coefficients})."""
        known = tuple(n for n in range(self.nodes) if n not in unknown)
        known = known[: self.k + self.nu]
        return known, {u: _solve(self.mds, known, u) for u in unknown}


# a pair's four symbols, the member with the larger x first
_PAIR = ((1, 0), (0, 1)) + generator_matrix(2, 2)[2:]
_ROLES = {"C_self": 0, "C_other": 1, "U_self": 2, "U_other": 3}


def _pair(first: bool, want: str, **have) -> np.ndarray:
    """One symbol of a pair from two others.  ``first`` says whether
    this node is the pair's first member (its x is the larger);
    ``have`` maps two of C_self, C_other, U_self, U_other to arrays
    (None = zeros)."""
    flip = 0 if first else 1
    names = sorted(have)
    index = tuple(_ROLES[name] ^ flip for name in names)
    coefs = _solve(_PAIR, index, _ROLES[want] ^ flip)
    return _combine(coefs, [have[name] for name in names])


def _check(guarantee: str) -> None:
    if guarantee not in ("kept", "broken"):
        raise ValueError(f"guarantee is 'kept' or 'broken', not {guarantee!r}")


# -- encode ---------------------------------------------------------------


def encode_shards(
    payload, k: int, m: int, chunk: int, d: int | None = None,
    guarantee: str = "kept",
) -> list[np.ndarray]:
    """The k+m shards of ``payload`` (zero-padded to whole stripes of
    k chunks): shard i is chunk i of every stripe, concatenated.  The
    parity chunks are what a layered decode of the m parity nodes
    gives (``ErasureCodeClay::encode_chunks``).  ``guarantee="broken"``
    is the control: the last data chunk is left out of the coupling
    (a companion reads zeros in its place), so the parity chunks are
    no longer ErasureCodeClay's."""
    _check(guarantee)
    g = _Grid(k, m, d)
    if chunk % g.planes:
        raise ValueError(f"a chunk of {chunk} is not {g.planes} whole sub-chunks")
    sc = chunk // g.planes
    data = np.frombuffer(payload, dtype=np.uint8)
    pad = -len(data) % (k * chunk)
    if pad:
        data = np.concatenate([data, np.zeros(pad, dtype=np.uint8)])
    stripes = len(data) // (k * chunk)
    shards = [
        np.ascontiguousarray(data.reshape(stripes, k, chunk)[:, i, :]).reshape(-1)
        for i in range(k)
    ] + [np.zeros(stripes * chunk, dtype=np.uint8) for _ in range(m)]
    # C[node][z]: sub-chunk z of every stripe, a view of the shard
    C = {
        g.node(i): shard.reshape(stripes, g.planes, sc).transpose(1, 0, 2)
        for i, shard in enumerate(shards)
    }
    for n in g.virtual:
        C[n] = [None] * g.planes
    left_out = g.node(k - 1) if guarantee == "broken" else None
    erased = set(range(k + g.nu, g.nodes))
    known, rows = g.mds_rows(erased)
    U = {n: [None] * g.planes for n in range(g.nodes)}

    def companion_of(n, z):
        x, y = n % g.q, n // g.q
        zy = g.digit(z, y)
        return y * g.q + zy, g.companion(z, x, y), x > zy

    by_score: dict[int, list[int]] = {}
    for z in range(g.planes):
        by_score.setdefault(g.score(z, erased), []).append(z)
    for score in sorted(by_score):
        # a companion that is erased lies in a plane of the score
        # before, so its C is there already
        for z in by_score[score]:
            for n in range(g.nodes):
                if n in erased:
                    continue
                p, zp, first = companion_of(n, z)
                if p == n:
                    U[n][z] = C[n][z]
                else:
                    U[n][z] = _pair(
                        first, "U_self", C_self=C[n][z],
                        C_other=None if p == left_out else C[p][zp],
                    )
            for e in erased:
                U[e][z] = _combine(rows[e], [U[n][z] for n in known])
        for z in by_score[score]:
            for e in erased:
                p, zp, first = companion_of(e, z)
                if p == e:
                    C[e][z][...] = U[e][z]
                elif p in erased:
                    C[e][z][...] = _pair(
                        first, "C_self", U_self=U[e][z], U_other=U[p][zp]
                    )
                else:
                    C[e][z][...] = _pair(
                        first, "C_self", U_self=U[e][z], C_other=C[p][zp]
                    )
    return shards


# -- repair ---------------------------------------------------------------


def repair_reads(
    lost: int, k: int, m: int, d: int | None = None
) -> list[tuple[int, int]]:
    """The sub-chunks every helper is asked for when chunk ``lost`` is
    repaired, as (first, count) runs: the planes in which the lost
    node is a dot, q^(t-1) of q^t
    (``ErasureCodeClay::get_repair_subchunks``)."""
    g = _Grid(k, m, d)
    n = g.node(lost)
    runs: list[tuple[int, int]] = []
    for z in range(g.planes):
        if g.digit(z, n // g.q) != n % g.q:
            continue
        if runs and sum(runs[-1]) == z:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1)
        else:
            runs.append((z, 1))
    return runs


def repair_shard(
    fragments: dict, lost: int, k: int, m: int, chunk: int,
    d: int | None = None, guarantee: str = "kept",
) -> np.ndarray:
    """Shard ``lost`` from d helpers' fragments: ``fragments`` maps a
    helper's chunk position to what ``repair_reads`` names of its
    shard, a stripe after another
    (``ErasureCodeClay::repair_one_lost_chunk``).  The chunks that are
    neither lost nor helpers are *aloof* (d < k + m - 1).  In a repair
    plane the U of the lost node's row and of the aloof nodes are the
    m unknowns of the plane's codeword; the lost node's own U is its
    C there, and each U of its row gives, with that helper's C, the
    lost node's C in one plane more.  ``guarantee="broken"`` is the
    control: the fragment of the last helper that is a known node's
    companion is left out of the coupling (the companion reads zeros
    in its place)."""
    _check(guarantee)
    g = _Grid(k, m, d)
    if chunk % g.planes:
        raise ValueError(f"a chunk of {chunk} is not {g.planes} whole sub-chunks")
    sc = chunk // g.planes
    if len(fragments) != g.d:
        raise ValueError(f"{len(fragments)} fragments, d={g.d}")
    ell = g.node(lost)
    planes = [z for first, count in repair_reads(lost, k, m, d)
              for z in range(first, first + count)]
    where = {z: i for i, z in enumerate(planes)}
    frag = {}
    for chunk_id, payload in fragments.items():
        arr = (
            np.frombuffer(bytes(payload), dtype=np.uint8)
            if isinstance(payload, (bytes, bytearray, memoryview))
            else np.ascontiguousarray(payload, dtype=np.uint8).reshape(-1)
        )
        if len(arr) % (len(planes) * sc):
            raise ValueError("a fragment is not whole stripes of the reads")
        frag[g.node(chunk_id)] = arr.reshape(-1, len(planes), sc).transpose(1, 0, 2)
    stripes = {f.shape[1] for f in frag.values()}
    if len(stripes) != 1 or ell in frag:
        raise ValueError("fragments must be of one length, and not the lost chunk's")
    (stripes,) = stripes
    for n in g.virtual:
        frag[n] = [None] * len(planes)
    aloof = set(range(g.nodes)) - set(frag) - {ell}
    row = {ell // g.q * g.q + x for x in range(g.q)}
    if row & aloof:
        raise ValueError("a node of the lost node's row is not a helper")
    unknown = row | aloof
    known, rows = g.mds_rows(unknown)
    left_out = None
    if guarantee == "broken":
        # the last helper that is some known node's companion: not of
        # the lost node's row, nor of a row otherwise aloof
        left_out = max(
            n for n in set(frag) - g.virtual - row
            if {n // g.q * g.q + x for x in range(g.q)} - aloof - {n}
        )
    U = {n: {} for n in range(g.nodes)}
    out = np.zeros((g.planes, stripes, sc), dtype=np.uint8)
    # an aloof companion's U comes from a plane of the score before
    for z in sorted(planes, key=lambda z: (g.score(z, aloof), z)):
        for n in range(g.nodes):
            if n in unknown:
                continue
            x, y = n % g.q, n // g.q
            zy = g.digit(z, y)
            p, zp = y * g.q + zy, g.companion(z, x, y)
            mine = frag[n][where[z]]
            if p == n:
                U[n][z] = mine
            elif p in aloof:
                U[n][z] = _pair(x > zy, "U_self", C_self=mine, U_other=U[p][zp])
            else:
                U[n][z] = _pair(
                    x > zy, "U_self", C_self=mine,
                    C_other=None if p == left_out else frag[p][where[zp]],
                )
        for u in unknown:
            U[u][z] = _combine(rows[u], [U[n][z] for n in known])
        out[z] = U[ell][z]
        for n in row - {ell}:
            # the lost node in the plane whose digit is this helper's x
            zp = g.companion(z, n % g.q, ell // g.q)
            out[zp] = _pair(
                ell > n, "C_self", C_other=frag[n][where[z]], U_other=U[n][z]
            )
    return np.ascontiguousarray(out.transpose(1, 0, 2)).reshape(-1)
