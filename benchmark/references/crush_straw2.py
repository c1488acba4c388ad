"""Plain reference for a whole-map remap: ``crush_do_rule`` for the
rule ``take root / chooseleaf firstn 0 type <domain> / emit`` over a
layered straw2 hierarchy under the jewel tunables (``mapper.c``), then
the OSDMap pipeline of a replicated pool with no upmap, pg_temp or
primary affinity (raw -> up -> acting, primary = first).

Vectorised over PGs with numpy; every retry is a masked pass.
Imports nothing of the program.  The one table that no formula gives,
``__LL_tbl`` of upstream's ``crush_ln_table.h``, is kept beside this
file as data.
"""

from __future__ import annotations

import decimal
import functools
import pathlib

import numpy as np

NONE = 0x7FFFFFFF
SEED = np.uint32(1315423911)
S64_MIN = np.iinfo(np.int64).min

# jewel tunables
CHOOSE_TOTAL_TRIES = 50
CHOOSELEAF_VARY_R = 1


# -- rjenkins1 ----------------------------------------------------------


def _mix(a, b, c):
    a = a - b; a = a - c; a = a ^ (c >> 13)  # noqa: E702
    b = b - c; b = b - a; b = b ^ (a << 8)  # noqa: E702
    c = c - a; c = c - b; c = c ^ (b >> 13)  # noqa: E702
    a = a - b; a = a - c; a = a ^ (c >> 12)  # noqa: E702
    b = b - c; b = b - a; b = b ^ (a << 16)  # noqa: E702
    c = c - a; c = c - b; c = c ^ (b >> 5)  # noqa: E702
    a = a - b; a = a - c; a = a ^ (c >> 3)  # noqa: E702
    b = b - c; b = b - a; b = b ^ (a << 10)  # noqa: E702
    c = c - a; c = c - b; c = c ^ (b >> 15)  # noqa: E702
    return a, b, c


def hash32_2(a, b):
    a = np.asarray(a).astype(np.uint32)
    b = np.asarray(b).astype(np.uint32)
    with np.errstate(over="ignore"):
        h = SEED ^ a ^ b
        x = np.uint32(231232)
        y = np.uint32(1232)
        a, b, h = _mix(a, b, h)
        x, a, h = _mix(x, a, h)
        b, y, h = _mix(b, y, h)
    return h


def hash32_3(a, b, c):
    a = np.asarray(a).astype(np.uint32)
    b = np.asarray(b).astype(np.uint32)
    c = np.asarray(c).astype(np.uint32)
    with np.errstate(over="ignore"):
        h = SEED ^ a ^ b ^ c
        x = np.uint32(231232)
        y = np.uint32(1232)
        a, b, h = _mix(a, b, h)
        c, x, h = _mix(c, x, h)
        y, a, h = _mix(y, a, h)
        b, x, h = _mix(b, x, h)
        y, c, h = _mix(y, c, h)
    return h


# -- crush_ln -----------------------------------------------------------


@functools.lru_cache(maxsize=2)
def ln_minus_table(log: str = "table") -> np.ndarray:
    """``crush_ln(u) - 2^48`` for every 16-bit draw u, as int64.
    ``log="float"`` is the control: the same quantity from a float64
    logarithm, without upstream's tables."""
    if log == "float":
        u = np.arange(65536, dtype=np.float64)
        return ((1 << 44) * np.log2(u + 1.0)).astype(np.int64) - (1 << 48)
    if log != "table":
        raise ValueError(f"log is 'table' or 'float', not {log!r}")
    text = (pathlib.Path(__file__).parent / "crush_ln_ll_tbl.txt").read_text()
    ll = [
        int(tok, 16)
        for line in text.splitlines()
        if not line.startswith("#")
        for tok in line.split()
    ]
    if len(ll) != 256:
        raise ValueError(f"__LL_tbl has {len(ll)} entries, not 256")
    rh = [-((-(1 << 55)) // (128 + k)) for k in range(129)]
    with decimal.localcontext() as ctx:
        ctx.prec = 60
        ln2 = decimal.Decimal(2).ln()
        ln128 = decimal.Decimal(128).ln()
        lh = [
            int((decimal.Decimal(128 + k).ln() - ln128) / ln2 * (1 << 48))
            for k in range(128)
        ] + [0xFFFF00000000]
    out = np.empty(65536, dtype=np.int64)
    for u in range(65536):
        x = u + 1
        iexpon = 15
        if not x & 0x18000:
            bits = 16 - (x & 0x1FFFF).bit_length()
            x <<= bits
            iexpon = 15 - bits
        k = (x >> 8) - 128
        index2 = ((x * rh[k]) >> 48) & 0xFF
        out[u] = (iexpon << 44) + ((lh[k] + ll[index2]) >> 4) - (1 << 48)
    return out


# -- the hierarchy ------------------------------------------------------


class Hierarchy:
    """root -> [racks ->] hosts -> osds, all straw2, every OSD of
    weight 1.0: the map ``crushtool --build N:per_host[:hosts_per_rack]``
    of the deployment stands for.  Bucket ids run -1, -2, ... in the
    order hosts, racks, root."""

    def __init__(self, num_osds: int, per_host: int, hosts_per_rack: int = 0):
        self.num_osds = num_osds
        buckets: list[tuple[int, list[int], list[int]]] = []  # type, items, weights

        def add(btype, items, weights):
            buckets.append((btype, items, weights))
            return -len(buckets)

        hosts = []
        for lo in range(0, num_osds, per_host):
            items = list(range(lo, min(lo + per_host, num_osds)))
            hosts.append(add(1, items, [0x10000] * len(items)))
        level = hosts
        if hosts_per_rack:
            level = [
                add(2, sub, [sum(buckets[-1 - b][2]) for b in sub])
                for sub in (
                    hosts[i : i + hosts_per_rack]
                    for i in range(0, len(hosts), hosts_per_rack)
                )
            ]
        self.root = add(3, level, [sum(buckets[-1 - b][2]) for b in level])
        width = max(len(b[1]) for b in buckets)
        n = len(buckets)
        self.items = np.zeros((n, width), dtype=np.int64)
        self.weights = np.zeros((n, width), dtype=np.int64)
        self.size = np.zeros(n, dtype=np.int64)
        self.btype = np.zeros(n, dtype=np.int64)
        for i, (btype, items, weights) in enumerate(buckets):
            self.items[i, : len(items)] = items
            self.weights[i, : len(items)] = weights
            self.size[i] = len(items)
            self.btype[i] = btype

    def item_type(self, item: np.ndarray) -> np.ndarray:
        return np.where(item >= 0, 0, self.btype[np.clip(-1 - item, 0, None)])

    def straw2_choose(self, bidx, x, r, ln_minus) -> np.ndarray:
        """bucket_straw2_choose for each lane: bucket index, input, r."""
        width = int(self.size[bidx].max())
        items = self.items[bidx, :width]
        weights = self.weights[bidx, :width]
        u = hash32_3(x[:, None], items, r[:, None]) & np.uint32(0xFFFF)
        ln = ln_minus[u]
        # div64_s64 truncates toward zero; ln <= 0 < weight
        draw = np.where(weights > 0, -((-ln) // np.maximum(weights, 1)), S64_MIN)
        pick = np.argmax(draw, axis=1)  # first of equal draws wins
        return items[np.arange(len(bidx)), pick]

    def descend(self, start, x, r, ttype: int, ln_minus) -> np.ndarray:
        """From bucket ``start`` choose downwards, with the same r, until
        an item of type ``ttype`` is reached."""
        item = np.array(start, dtype=np.int64)
        todo = self.item_type(item) != ttype
        while todo.any():
            idx = np.nonzero(todo)[0]
            if (item[idx] >= 0).any():
                raise ValueError("descent passed a device above the wanted type")
            item[idx] = self.straw2_choose(-1 - item[idx], x[idx], r[idx], ln_minus)
            todo[idx] = self.item_type(item[idx]) != ttype
        return item


def is_out(weight: np.ndarray, item: np.ndarray, x: np.ndarray) -> np.ndarray:
    w = weight[np.clip(item, 0, len(weight) - 1)]
    draw = (hash32_2(x, item) & np.uint32(0xFFFF)).astype(np.int64)
    return (item >= len(weight)) | (w == 0) | ((w < 0x10000) & (draw >= w))


def chooseleaf_firstn(
    h: Hierarchy,
    x: np.ndarray,
    numrep: int,
    domain: int,
    weight: np.ndarray,
    log: str = "table",
) -> tuple[np.ndarray, np.ndarray]:
    """``take root; chooseleaf firstn 0 type domain; emit`` for every
    input of ``x`` (uint32).  Returns (devices (N, numrep) padded with
    NONE, counts (N,))."""
    ln_minus = ln_minus_table(log)
    x = np.asarray(x).astype(np.uint32)
    weight = np.asarray(weight, dtype=np.int64)
    n = len(x)
    tries = CHOOSE_TOTAL_TRIES + 1
    out_dom = np.full((n, numrep), NONE, dtype=np.int64)
    out_leaf = np.full((n, numrep), NONE, dtype=np.int64)
    cnt = np.zeros(n, dtype=np.int64)
    cols = np.arange(numrep)
    for rep in range(numrep):
        ftotal = np.zeros(n, dtype=np.int64)
        pending = np.ones(n, dtype=bool)
        while pending.any():
            idx = np.nonzero(pending)[0]
            xi = x[idx]
            r = (rep + ftotal[idx]).astype(np.uint32)
            dom = h.descend(np.full(len(idx), h.root), xi, r, domain, ln_minus)
            taken = cols[None, :] < cnt[idx, None]
            fail = ((out_dom[idx] == dom[:, None]) & taken).any(axis=1)
            # the recursive call: one try (descend_once), stable, r' = r
            sub_r = r >> np.uint32(CHOOSELEAF_VARY_R - 1)
            leaf = h.descend(dom, xi, sub_r, 0, ln_minus)
            fail |= ((out_leaf[idx] == leaf[:, None]) & taken).any(axis=1)
            fail |= is_out(weight, leaf, xi)
            good = idx[~fail]
            out_dom[good, cnt[good]] = dom[~fail]
            out_leaf[good, cnt[good]] = leaf[~fail]
            cnt[good] += 1
            pending[good] = False
            bad = idx[fail]
            ftotal[bad] += 1
            pending[bad[ftotal[bad] >= tries]] = False  # skip_rep
    return out_leaf, cnt


def pool_pps(ps: np.ndarray, pool_id: int, pgp_num: int) -> np.ndarray:
    """``pg_pool_t::raw_pg_to_pps`` with hashpspool set."""
    mask = (1 << (pgp_num - 1).bit_length()) - 1
    lo = ps & mask
    m = np.where(lo < pgp_num, lo, ps & (mask >> 1))
    return hash32_2(m, pool_id)


def replicated_up_acting(
    h: Hierarchy,
    ps: np.ndarray,
    *,
    pool_id: int,
    pgp_num: int,
    size: int,
    domain: int,
    osd_weight,
    osd_up,
    log: str = "table",
):
    """(up (N, size) padded with NONE, up_primary (N,)) of the PGs
    ``ps``; acting equals up where nothing overrides it."""
    pps = pool_pps(np.asarray(ps, dtype=np.int64), pool_id, pgp_num)
    raw, cnt = chooseleaf_firstn(h, pps, size, domain, osd_weight, log)
    up_ok = np.append(np.asarray(osd_up, dtype=bool), False)
    alive = (np.arange(size)[None, :] < cnt[:, None]) & up_ok[
        np.clip(raw, 0, len(up_ok) - 1)
    ]
    order = np.argsort(~alive, axis=1, kind="stable")
    up = np.where(
        np.take_along_axis(alive, order, axis=1),
        np.take_along_axis(raw, order, axis=1),
        NONE,
    )
    primary = np.where(alive.any(axis=1), up[:, 0], -1)
    return up, primary
