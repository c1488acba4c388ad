"""Batched CRUSH on device — the ParallelPGMapper replacement.

The reference recomputes every PG's placement by sharding pgid ranges
over a thread pool (src/osd/OSDMapMapping.h:18-156); here the whole map
compiles to dense arrays and ``crush_do_rule`` becomes a scalar-traced
function vmapped over the PG batch: one device call maps a million PGs.

Scope: all five bucket algorithms (straw2/uniform/straw/list/tree),
tunables with
choose_local_tries == choose_local_fallback_tries == 0 (true of every
profile since bobtail), rule programs of [SET_*...] TAKE CHOOSE[LEAF]
EMIT groups.  Anything else raises UnsupportedMap and callers fall back
to the exact Python oracle (ceph_tpu.crush.mapper) — the same
plugin-style split the EC backends use.

Exactness: every table lookup is a float32 one-hot matmul over
24-bit-split tables (exact in the f32 mantissa), and all fixed-point
arithmetic runs on float64 integers within the 2^53-exact range —
see CompiledMap and _crush_ln_f64.  Same r'-advancement and retry
semantics as mapper.c; verified against the oracle in
tests/test_crush_jax.py (and _crush_ln_f64 value-exact over the full
u16 domain).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import math
import time
from dataclasses import dataclass

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from ..common import tracing  # noqa: E402
from .hashing import _mix_inner  # noqa: E402
from .ln import _tables as _ln_tables  # noqa: E402
from .types import (  # noqa: E402
    CRUSH_BUCKET_LIST,
    CRUSH_BUCKET_STRAW,
    CRUSH_BUCKET_STRAW2,
    CRUSH_BUCKET_TREE,
    CRUSH_BUCKET_UNIFORM,
    CRUSH_ITEM_NONE,
    CRUSH_ITEM_UNDEF,
    CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP,
    CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP,
    CRUSH_RULE_EMIT,
    CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    CRUSH_RULE_SET_CHOOSELEAF_VARY_R,
    CRUSH_RULE_SET_CHOOSELEAF_STABLE,
    CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
    CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
    CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_TAKE,
)

MAX_DEPTH = 16  # CRUSH_MAX_DEPTH is 10; headroom is free in a fori

class UnsupportedMap(ValueError):
    """Map/rule shape outside the device kernel's scope; use the oracle."""


# -- device-side primitives ------------------------------------------------


def _hash3(a, b, c):
    """rjenkins1 arity 3 on uint32 jnp values (hash.c:48-59)."""
    h = jnp.uint32(1315423911) ^ a ^ b ^ c
    x0, y0 = jnp.uint32(231232), jnp.uint32(1232)
    a, b, h = _mix_inner(a, b, h)
    c, x, h = _mix_inner(c, x0, h)
    y, a, h = _mix_inner(y0, a, h)
    b, x, h = _mix_inner(b, x, h)
    y, c, h = _mix_inner(y, c, h)
    return h.astype(jnp.uint32)


def _hash2(a, b):
    """rjenkins1 arity 2 (hash.c:37-46)."""
    h = jnp.uint32(1315423911) ^ a ^ b
    x0, y0 = jnp.uint32(231232), jnp.uint32(1232)
    a, b, h = _mix_inner(a, b, h)
    x, a, h = _mix_inner(x0, a, h)
    b, y, h = _mix_inner(b, y0, h)
    return h.astype(jnp.uint32)


def _hash4(a, b, c, d):
    """rjenkins1 arity 4 (hash.c:61-74) — the list chooser's hash."""
    h = jnp.uint32(1315423911) ^ a ^ b ^ c ^ d
    x0, y0 = jnp.uint32(231232), jnp.uint32(1232)
    a, b, h = _mix_inner(a, b, h)
    c, d, h = _mix_inner(c, d, h)
    a, x, h = _mix_inner(a, x0, h)
    y, b, h = _mix_inner(y0, b, h)
    c, x, h = _mix_inner(c, x, h)
    y, d, h = _mix_inner(y, d, h)
    return h.astype(jnp.uint32)


# A straw2-logarithm table index is read as two digits, the low one
# of _LN_LO_BITS bits: _ln_lookup finds row (hi << _LN_LO_BITS) + lo of
# a table that _fold_ln_table folded.
_LN_LO_BITS = 3
_LN_RADIX = 1 << _LN_LO_BITS


def _fold_ln_table(cols) -> np.ndarray:
    """(rows,) int columns -> the (ceil(rows / 8), 8 * C) f32 table
    _ln_lookup reads: folded row h holds rows 8h .. 8h+7 side by side
    (zeros past the end).  Every value must be f32-exact (<= 2^24)."""
    tbl = np.stack(cols, axis=1)
    assert tbl.max() <= 1 << 24 and tbl.min() >= 0
    rows, ncols = tbl.shape
    n_hi = -(-rows // _LN_RADIX)
    tbl = np.concatenate(
        [tbl, np.zeros((n_hi * _LN_RADIX - rows, ncols), tbl.dtype)]
    )
    return tbl.reshape(n_hi, _LN_RADIX * ncols).astype(np.float32)


def _ln_lookup(idx, tbl):
    """Row ``idx`` of a folded table for every element of ``idx``:
    (..., C) f32, exact.

    The high digit picks a folded row through a one-hot matmul (f32
    HIGHEST: a one-hot times an integer <= 2^24 is exact), the low one
    picks one of that row's 8 groups through a select-sum that the
    compiler fuses into the matmul's output.  What this pins is the
    layout.  The callers vmap this over PGs x replicas x items, and
    the PG batch has to stay in the 128 lanes through both operands
    and the result.  The compiler puts a one-hot's table axis there
    instead as soon as that axis fills them — a 128, 255, 256, 384 or
    512 row one-hot compiled so, 120, 129, 136, 257 and 264 rows did
    not (jax 0.9.0, libtpu 0.0.34) — and then spreads every lane's
    index over all of them: for the 256-row table that took 9 x the
    time of the same look-up the right way round (PERF.md section 6,
    PR 27).  No one-hot here is wider than ceil(rows / 8), 32 and 17
    for the two tables, whatever a table's row count;
    tests/test_chip_compile.py holds that at three item widths."""
    n_hi, width = tbl.shape
    hi_hot = (
        jnp.arange(n_hi) == (idx >> _LN_LO_BITS)[..., None]
    ).astype(jnp.float32)
    folded = jnp.matmul(
        hi_hot, tbl, precision=lax.Precision.HIGHEST
    ).reshape(*idx.shape, _LN_RADIX, width // _LN_RADIX)
    lo_hot = jnp.arange(_LN_RADIX) == (idx & (_LN_RADIX - 1))[..., None]
    return jnp.sum(jnp.where(lo_hot[..., None], folded, 0.0), axis=-2)


def _crush_ln_f64(u, ln_tbl1, ln_tbl2):
    """2^44*log2(u+1) exactly, in float64 (mapper.c:248-290).

    The two table look-ups (_ln_lookup) return whole 24-bit halves —
    rh_hi (25 bits: RH[0] = 2^48), rh_lo, lh_hi, lh_lo, then ll_hi,
    ll_lo — and everything after them runs on f64 integers < 2^53.
    index2 reproduces ((x*RH) >> 48) & 0xff via the 24-bit split (the
    C's int64 wraparound only ever touches bits that the mod-256
    discards).  Value-exact against ceph_tpu.crush.ln.crush_ln over
    the full u16 domain, flat and under the callers' double vmap
    (tests/test_crush_jax.py)."""
    x = u.astype(jnp.int32) + 1
    masked = x & 0x1FFFF
    nbits = jnp.zeros_like(x)
    for shift in (16, 8, 4, 2, 1):
        step = (masked >> shift) != 0
        nbits = nbits + jnp.where(step, shift, 0)
        masked = jnp.where(step, masked >> shift, masked)
    bitlen = nbits + (masked != 0)
    shift_amt = jnp.where((x & 0x18000) == 0, 16 - bitlen, 0)
    x = x << shift_amt
    iexp = 15 - shift_amt
    k = ((x >> 8) << 1) - 256 >> 1
    b1 = _ln_lookup(k, ln_tbl1).astype(jnp.float64)
    rh_hi, rh_lo = b1[..., 0], b1[..., 1]
    lh_v = b1[..., 2] * float(1 << 24) + b1[..., 3]
    xf = x.astype(jnp.float64)
    T = xf * rh_hi + jnp.floor(xf * rh_lo / float(1 << 24))
    index2 = jnp.mod(
        jnp.floor(T / float(1 << 24)), 256.0
    ).astype(jnp.int32)
    b2 = _ln_lookup(index2, ln_tbl2).astype(jnp.float64)
    ll_v = b2[..., 0] * float(1 << 24) + b2[..., 1]
    return iexp.astype(jnp.float64) * float(1 << 44) + jnp.floor(
        (lh_v + ll_v) / 16.0
    )


# -- map compilation -------------------------------------------------------


@dataclass(frozen=True)
class CompiledMap:
    """Dense-array rendering of a CrushMap for the device kernel.

    All hot-path tables are float32, consumed through one-hot matmuls:
    dynamic gathers are pathologically slow on TPU (measured ~20 ns per
    gathered element vs ~1 ns through the MXU), and every value fits a
    float32 mantissa exactly after the 24-bit splits below, so lookups
    stay bit-exact.  Downstream arithmetic runs in float64 whose
    integer range (2^53) covers the 2^48 fixed-point ln values.
    """

    # (nb, 7*sz+3) f32:
    # items|w_hi|w_lo|straw_hi|straw_lo|sum_hi|sum_lo|size|alg|id
    # (straw columns: legacy straw lengths; sum columns: the list
    # chooser's tail sums — zero outside their algs)
    row_pack: jnp.ndarray
    # choose_args rendering (crush.h:248-293): per-position straw2
    # weight replacements + hash-id remaps, position-clamped at compile
    # time.  None when the map carries no choose_args (zero overhead).
    args_pack: jnp.ndarray | None  # (nb, P*2*sz + sz) f32: aw_hi|aw_lo|aids
    arg_positions: int  # P (max weight_set positions; 0 without args)
    types_f: jnp.ndarray  # (nb,) f32 bucket types
    bidx_f: jnp.ndarray  # (max_neg,) f32: (-1-id) -> row, -1 for gaps
    # crush_ln's tables as 24-bit halves, folded by _fold_ln_table
    ln_tbl1: jnp.ndarray  # (17, 8*4) f32: rh_hi, rh_lo, lh_hi, lh_lo
    ln_tbl2: jnp.ndarray  # (32, 8*2) f32: ll_hi, ll_lo
    sz: int
    nb: int
    has_uniform: bool
    has_straw: bool
    has_list: bool
    has_tree: bool
    # tree buckets: (nb, 2*tree_nodes + 1) f32 nw_hi|nw_lo|start_n
    tree_pack: jnp.ndarray | None
    tree_nodes: int
    uniform_sz: int  # max uniform-bucket size (perm loop bound)
    bidx: tuple  # host-side (-1-id) -> row for TAKE resolution
    max_devices: int
    tunables: tuple  # (total_tries, descend_once, vary_r, stable)
    rules: tuple  # immutable rule description for cache keys
    # host-side structure (per row): items/sizes/types for the fast
    # path's descent-depth analysis, and the source CrushMap for the
    # exact-oracle fallback on speculation overflow
    np_items: np.ndarray
    np_sizes: np.ndarray
    np_types: np.ndarray
    np_algs: np.ndarray
    source: object
    source_mutation: int
    # structural cache key: everything the TRACED program depends on
    # except the numeric weight tables (row_pack/args_pack/tree_pack
    # are jit operands), so weights-only epoch changes reuse the
    # compiled kernel instead of paying a recompile per epoch
    skey: tuple

    def __hash__(self):
        return hash(self.skey)

    def __eq__(self, other):
        return (
            isinstance(other, CompiledMap) and self.skey == other.skey
        )


def compile_map(cmap) -> CompiledMap:
    """CrushMap -> dense arrays; raises UnsupportedMap outside scope."""
    t = cmap.tunables
    if t.choose_local_tries or t.choose_local_fallback_tries:
        raise UnsupportedMap(
            "choose_local_(fallback_)tries != 0 needs the legacy perm "
            "fallback; use the oracle"
        )
    if not cmap.buckets:
        raise UnsupportedMap("empty map")
    for b in cmap.buckets.values():
        if b.alg not in (
            CRUSH_BUCKET_STRAW2,
            CRUSH_BUCKET_UNIFORM,
            CRUSH_BUCKET_STRAW,
            CRUSH_BUCKET_LIST,
            CRUSH_BUCKET_TREE,
        ):
            raise UnsupportedMap(
                f"bucket {b.id} alg {b.alg}: unknown bucket alg"
            )
    nb = len(cmap.buckets)
    sz = max(max(b.size for b in cmap.buckets.values()), 1)
    items = np.zeros((nb, sz), dtype=np.int64)
    weights = np.zeros((nb, sz), dtype=np.int64)
    straws = np.zeros((nb, sz), dtype=np.int64)
    sums = np.zeros((nb, sz), dtype=np.int64)
    sizes = np.zeros(nb, dtype=np.int64)
    types = np.zeros(nb, dtype=np.int64)
    algs = np.zeros(nb, dtype=np.int64)
    ids = np.zeros(nb, dtype=np.int64)
    max_neg = max(-b.id for b in cmap.buckets.values())
    bidx = np.full(max_neg, -1, dtype=np.int64)
    for row, b in enumerate(
        sorted(cmap.buckets.values(), key=lambda b: -b.id)
    ):
        items[row, : b.size] = b.items
        weights[row, : b.size] = b.item_weights
        sizes[row] = b.size
        types[row] = b.type
        algs[row] = b.alg
        ids[row] = b.id
        bidx[-1 - b.id] = row
        if b.size and max(abs(i) for i in b.items) >= 1 << 24:
            raise UnsupportedMap("item id magnitude >= 2^24")
        if abs(b.id) >= 1 << 24:
            raise UnsupportedMap("bucket id magnitude >= 2^24")
        if b.weight >= 1 << 32:
            raise UnsupportedMap("bucket weight >= 2^32")
        if b.alg == CRUSH_BUCKET_STRAW:
            if not b.straws or len(b.straws) < b.size:
                raise UnsupportedMap(
                    f"straw bucket {b.id} missing straw table"
                )
            if any(s >= 1 << 32 for s in b.straws[: b.size]):
                raise UnsupportedMap("straw length >= 2^32")
            straws[row, : b.size] = b.straws[: b.size]
        if b.alg == CRUSH_BUCKET_LIST:
            if not b.sum_weights or len(b.sum_weights) < b.size:
                raise UnsupportedMap(
                    f"list bucket {b.id} missing sum_weights"
                )
            if any(s >= 1 << 32 for s in b.sum_weights[: b.size]):
                raise UnsupportedMap("list sum weight >= 2^32")
            sums[row, : b.size] = b.sum_weights[: b.size]
        if b.alg == CRUSH_BUCKET_TREE and not b.node_weights:
            raise UnsupportedMap(
                f"tree bucket {b.id} missing node_weights"
            )

    # tree buckets: per-bucket node-weight tables + start node
    has_tree = bool((algs == CRUSH_BUCKET_TREE).any())
    tree_pack = None
    tree_nodes = 0
    if has_tree:
        tree_nodes = max(
            len(b.node_weights)
            for b in cmap.buckets.values()
            if b.alg == CRUSH_BUCKET_TREE
        )
        nw = np.zeros((nb, tree_nodes), dtype=np.int64)
        start = np.zeros(nb, dtype=np.int64)
        for row, b in enumerate(
            sorted(cmap.buckets.values(), key=lambda b: -b.id)
        ):
            if b.alg != CRUSH_BUCKET_TREE:
                continue
            if any(w >= 1 << 32 for w in b.node_weights):
                raise UnsupportedMap("tree node weight >= 2^32")
            nw[row, : len(b.node_weights)] = b.node_weights
            start[row] = len(b.node_weights) >> 1
        tree_pack = np.concatenate(
            [
                (nw >> 16).astype(np.float32),
                (nw & 0xFFFF).astype(np.float32),
                start[:, None].astype(np.float32),
            ],
            axis=1,
        )

    # choose_args → dense per-position weight/id tables.  The C only
    # consults args in the straw2 chooser (crush_bucket_choose,
    # mapper.c:387-418), so args on other bucket algs are ignored, and
    # the position clamp (get_choose_arg_weights, mapper.c:311-317) is
    # baked in by replicating each bucket's last weight-set row.
    P = 0
    args_pack = None
    if cmap.choose_args:
        P = max(
            (
                len(a.weight_set)
                for a in cmap.choose_args.values()
                if a.weight_set
            ),
            default=1,
        )
        aw = np.repeat(weights[:, None, :], P, axis=1)  # (nb, P, sz)
        aids = items.copy()
        for bid, arg in cmap.choose_args.items():
            b = cmap.buckets.get(bid)
            if b is None or b.alg != CRUSH_BUCKET_STRAW2:
                continue  # the C consults args only for straw2
            row = int(np.nonzero(ids == bid)[0][0])
            # empty weight_set falls back to bucket weights (the C's
            # weight_set_positions == 0 case)
            if arg.weight_set:
                for p in range(P):
                    ws = arg.weight_set[min(p, len(arg.weight_set) - 1)]
                    if len(ws) != b.size:
                        raise UnsupportedMap(
                            f"choose_arg weight_set size {len(ws)} != "
                            f"bucket {b.id} size {b.size}"
                        )
                    if any(w >= 1 << 32 for w in ws):
                        raise UnsupportedMap("choose_arg weight >= 2^32")
                    aw[row, p, : b.size] = ws
            if arg.ids is not None:
                if len(arg.ids) != b.size:
                    raise UnsupportedMap(
                        f"choose_arg ids size {len(arg.ids)} != "
                        f"bucket {b.id} size {b.size}"
                    )
                if any(abs(i) >= 1 << 24 for i in arg.ids):
                    raise UnsupportedMap(
                        "choose_arg id magnitude >= 2^24"
                    )
                aids[row, : b.size] = arg.ids
        args_pack = np.concatenate(
            [
                (aw >> 16).reshape(nb, P * sz).astype(np.float32),
                (aw & 0xFFFF).reshape(nb, P * sz).astype(np.float32),
                aids.astype(np.float32),
            ],
            axis=1,
        )

    rules = []
    for rule in cmap.rules:
        rules.append(None if rule is None else _compile_rule(rule))

    row_pack = np.concatenate(
        [
            items.astype(np.float32),
            (weights >> 16).astype(np.float32),
            (weights & 0xFFFF).astype(np.float32),
            (straws >> 16).astype(np.float32),
            (straws & 0xFFFF).astype(np.float32),
            (sums >> 16).astype(np.float32),
            (sums & 0xFFFF).astype(np.float32),
            sizes[:, None].astype(np.float32),
            algs[:, None].astype(np.float32),
            ids[:, None].astype(np.float32),
        ],
        axis=1,
    )
    rh, lh, ll = _ln_tables()
    ln_tbl1 = _fold_ln_table(
        [rh >> 24, rh & 0xFFFFFF, lh >> 24, lh & 0xFFFFFF]
    )
    ln_tbl2 = _fold_ln_table([ll >> 24, ll & 0xFFFFFF])
    skey = (
        sz,
        nb,
        cmap.max_devices,
        P,
        tree_nodes,
        items.tobytes(),
        sizes.tobytes(),
        types.tobytes(),
        algs.tobytes(),
        ids.tobytes(),
        bidx.tobytes(),
        (
            t.choose_total_tries + 1,
            t.chooseleaf_descend_once,
            t.chooseleaf_vary_r,
            t.chooseleaf_stable,
        ),
        tuple(rules),
    )
    return CompiledMap(
        row_pack=jnp.asarray(row_pack),
        args_pack=None if args_pack is None else jnp.asarray(args_pack),
        arg_positions=P,
        types_f=jnp.asarray(types.astype(np.float32)),
        bidx_f=jnp.asarray(bidx.astype(np.float32)),
        ln_tbl1=jnp.asarray(ln_tbl1),
        ln_tbl2=jnp.asarray(ln_tbl2),
        sz=sz,
        nb=nb,
        has_uniform=bool((algs == CRUSH_BUCKET_UNIFORM).any()),
        has_straw=bool((algs == CRUSH_BUCKET_STRAW).any()),
        has_list=bool((algs == CRUSH_BUCKET_LIST).any()),
        has_tree=has_tree,
        tree_pack=(
            None if tree_pack is None else jnp.asarray(tree_pack)
        ),
        tree_nodes=tree_nodes,
        uniform_sz=int(
            sizes[algs == CRUSH_BUCKET_UNIFORM].max()
        )
        if (algs == CRUSH_BUCKET_UNIFORM).any()
        else 0,
        bidx=tuple(int(v) for v in bidx),
        max_devices=cmap.max_devices,
        tunables=(
            t.choose_total_tries + 1,
            t.chooseleaf_descend_once,
            t.chooseleaf_vary_r,
            t.chooseleaf_stable,
        ),
        rules=tuple(rules),
        np_items=items,
        np_sizes=sizes,
        np_types=types,
        np_algs=algs,
        source=cmap,
        source_mutation=getattr(cmap, "mutation", 0),
        skey=skey,
    )


def _compile_rule(rule):
    """Rule -> tuple of (op, arg1, arg2) groups: [set-overrides..., take,
    choose, emit] repeated; raises UnsupportedMap on other shapes."""
    groups = []
    overrides = {}
    take = None
    choose = None
    for step in rule.steps:
        if step.op in (
            CRUSH_RULE_SET_CHOOSE_TRIES,
            CRUSH_RULE_SET_CHOOSELEAF_TRIES,
            CRUSH_RULE_SET_CHOOSELEAF_VARY_R,
            CRUSH_RULE_SET_CHOOSELEAF_STABLE,
            CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
            CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
        ):
            if step.op in (
                CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES,
                CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES,
            ):
                if step.arg1 > 0:
                    raise UnsupportedMap("local tries override")
                continue
            # the C applies tries overrides only when > 0 and
            # vary_r/stable only when >= 0 (mapper.c:963-991)
            if step.op in (
                CRUSH_RULE_SET_CHOOSE_TRIES,
                CRUSH_RULE_SET_CHOOSELEAF_TRIES,
            ):
                if step.arg1 > 0:
                    overrides[step.op] = step.arg1
            elif step.arg1 >= 0:
                overrides[step.op] = step.arg1
        elif step.op == CRUSH_RULE_TAKE:
            take = step.arg1
        elif step.op in (
            CRUSH_RULE_CHOOSE_FIRSTN,
            CRUSH_RULE_CHOOSELEAF_FIRSTN,
            CRUSH_RULE_CHOOSE_INDEP,
            CRUSH_RULE_CHOOSELEAF_INDEP,
        ):
            if take is None or choose is not None:
                raise UnsupportedMap("rule shape: choose without take")
            choose = (step.op, step.arg1, step.arg2)
        elif step.op == CRUSH_RULE_EMIT:
            if take is None or choose is None:
                raise UnsupportedMap("rule shape: emit without choose")
            groups.append(
                (take, choose, tuple(sorted(overrides.items())))
            )
            take = choose = None
        else:
            raise UnsupportedMap(f"rule op {step.op}")
    if take is not None or choose is not None:
        raise UnsupportedMap("rule does not end with EMIT")
    return tuple(groups)


# -- the kernel ------------------------------------------------------------

# Speculation bounds for the fast firstn path.  _SPEC_TRIES extra
# retries per replica are precomputed; a lane that needs more falls
# back to the exact host oracle (flagged via the kernel's ok output).
# P(fallback) per replica is roughly p_collision^_SPEC_TRIES, so for
# any realistically-sized map the fallback never fires; tiny test maps
# hit it occasionally and stay exact through the oracle.
_SPEC_TRIES = 8
_LEAF_SPEC = 4  # max speculated chooseleaf retries (descend_once => 1)
_SPEC_BUDGET = 512  # max speculative draws per lane per rule group

_K_FOUND, _K_BAD, _K_RETRY, _K_OVER = 0, 1, 2, 3


def _descent_steps(cm: CompiledMap, start_rows, ttype: int):
    """Per-level reachable bucket sets for a descent from
    ``start_rows`` toward ``ttype``, from the static bucket graph.

    Returns (steps, found_rows) where steps[i] describes the buckets a
    descent can be drawing from at its i-th draw — the fast path
    specializes each draw round to that set (row one-hot over the set
    instead of the whole map, item vectors sized to the set's max
    bucket) — and found_rows is the set of target-type buckets the
    descent can land on (the chooseleaf domains).  Returns (None,
    None) when a cycle (or > MAX_DEPTH chain) makes the static level
    structure unbounded.  A draw that lands on a bucket of the target
    type (ttype != 0) terminates; for ttype == 0 only devices
    terminate."""
    sizes, types, items = cm.np_sizes, cm.np_types, cm.np_items
    bidx = cm.bidx
    cur = set(start_rows)
    steps = []
    found: set = set()
    while cur:
        if len(steps) >= MAX_DEPTH:
            return None, None
        rows = tuple(sorted(cur))
        steps.append(
            {
                "rows": rows,
                "sz": max(
                    (int(sizes[r]) for r in rows), default=1
                )
                or 1,
                "algs": tuple(
                    sorted({int(cm.np_algs[r]) for r in rows})
                ),
                "usz": max(
                    (
                        int(sizes[r])
                        for r in rows
                        if int(cm.np_algs[r]) == CRUSH_BUCKET_UNIFORM
                    ),
                    default=0,
                )
                or 1,
            }
        )
        nxt: set = set()
        for row in cur:
            for it in items[row, : sizes[row]]:
                it = int(it)
                if it >= 0:
                    continue  # device: terminal
                neg = -1 - it
                if neg >= len(bidx) or bidx[neg] < 0:
                    continue  # invalid item: terminal
                r2 = bidx[neg]
                if ttype != 0 and types[r2] == ttype:
                    found.add(r2)
                    continue
                nxt.add(r2)
        cur = nxt
    return steps, found


def _plan_groups(
    cm: CompiledMap, ruleno: int, result_max: int, spec_boost: int = 0
):
    """Host-side pre-pass over a rule's groups: resolve TAKE rows,
    tries/tunables, and decide per group whether the speculative fast
    path applies (firstn, acyclic bounded-depth descent, single
    choose_args position)."""
    groups = cm.rules[ruleno]
    if groups is None:
        raise UnsupportedMap(f"no rule {ruleno}")
    total_tries, descend_once, vary_r_t, stable_t = cm.tunables
    plans = []
    for take, (op, arg1, arg2), overrides in groups:
        ov = dict(overrides)
        tries = ov.get(CRUSH_RULE_SET_CHOOSE_TRIES, total_tries)
        leaf_override = ov.get(CRUSH_RULE_SET_CHOOSELEAF_TRIES, 0)
        vary_r = ov.get(CRUSH_RULE_SET_CHOOSELEAF_VARY_R, vary_r_t)
        stable = ov.get(CRUSH_RULE_SET_CHOOSELEAF_STABLE, stable_t)
        numrep = arg1 if arg1 > 0 else result_max + arg1
        if numrep <= 0:
            continue
        nslots = min(numrep, result_max)
        if take >= 0:
            raise UnsupportedMap("TAKE of a device (not a bucket)")
        if -1 - take >= len(cm.bidx):
            raise UnsupportedMap(f"TAKE of unknown bucket {take}")
        take_row = cm.bidx[-1 - take]
        if take_row < 0:
            raise UnsupportedMap(f"TAKE of unknown bucket {take}")
        firstn = op in (
            CRUSH_RULE_CHOOSE_FIRSTN,
            CRUSH_RULE_CHOOSELEAF_FIRSTN,
        )
        leaf = op in (
            CRUSH_RULE_CHOOSELEAF_FIRSTN,
            CRUSH_RULE_CHOOSELEAF_INDEP,
        )
        if firstn:
            if leaf_override:
                leaf_tries = leaf_override
            elif descend_once:
                leaf_tries = 1
            else:
                leaf_tries = tries
        else:
            leaf_tries = leaf_override if leaf_override else 1
        plan = {
            "take_row": take_row,
            "ttype": arg2,
            "numrep": numrep,
            "nslots": nslots,
            "tries": tries,
            "leaf_tries": leaf_tries,
            "vary_r": vary_r,
            "stable": stable,
            "firstn": firstn,
            "leaf": leaf,
            "fast": None,
        }
        plans.append(plan)
        # -- fast-path qualification ----------------------------------
        if not firstn or cm.arg_positions > 1:
            continue  # multi-position choose_args keeps the generic path
        if leaf and arg2 == 0:
            continue  # chooseleaf targeting devices: degenerate shape
        outer_steps, domains = _descent_steps(cm, [take_row], arg2)
        if outer_steps is None or len(outer_steps) > MAX_DEPTH - 1:
            continue
        # Adaptive speculation width: the retry probability per
        # replica is roughly numrep / (number of distinct targets), so
        # wide maps (many hosts) need only a couple of speculated
        # retries while narrow test maps need the full window.  Sized
        # so the expected oracle-fallback count stays ~10 lanes per
        # million mapped PGs.
        if arg2 == 0:
            ntargets = max(cm.max_devices, 1)
        else:
            ntargets = max(len(domains), 1)
        p_retry = min(numrep / ntargets, 0.9)
        if spec_boost:
            # caller passed a non-trivial reweight vector: is_out()
            # rejects add retry pressure the topology-derived estimate
            # cannot see, so take the full speculation window
            spec = _SPEC_TRIES
        else:
            spec = max(
                2,
                min(
                    _SPEC_TRIES,
                    math.ceil(
                        math.log(1e-5 / max(numrep, 1))
                        / math.log(max(p_retry, 1e-9))
                    )
                    - 1,
                ),
            )
        r0 = min(numrep + spec, numrep + tries - 1)
        fast = {
            "R0": r0,
            "outer_steps": outer_steps,
        }
        draws = r0 * len(outer_steps)
        if leaf:
            leaf_steps, _ = _descent_steps(cm, sorted(domains), 0)
            if leaf_steps is None or len(leaf_steps) > MAX_DEPTH - 1:
                continue
            l0 = min(leaf_tries, _LEAF_SPEC)
            pd = 1 if stable else nslots
            fast.update(
                {"leaf_steps": leaf_steps, "L0": l0, "Pd": pd}
            )
            draws += r0 * pd * l0 * len(leaf_steps)
        if draws > _SPEC_BUDGET:
            continue
        plan["fast"] = fast
    return plans


def _make_rule_fn(
    cm: CompiledMap, ruleno: int, result_max: int, spec_boost: int = 0
):
    """Build the scalar-traced do_rule for one (map, rule, result_max).

    Returns ``rule_fn(x, weightv, row_pack, args_pack, tree_pack) ->
    (result, count, ok)``.  The numeric tables are jit OPERANDS so
    weights-only epoch changes reuse the compiled kernel (keyed on
    CompiledMap.skey); ``ok`` is False for lanes whose firstn retry
    chain outran the speculation window (callers re-map those through
    the exact host oracle — see batch_do_rule).

    Two execution strategies per rule group:

    * FAST (firstn groups on acyclic maps): because crush_choose_firstn
      uses r' = rep + ftotal at EVERY level of one descent, the whole
      descent outcome is a function of r' alone — so all candidate
      descents for r' = 0..R0-1 are precomputed in D_outer batched
      draw rounds (and the chooseleaf descents likewise, indexed by the
      outer r' that chose the domain), then a while_loop replays the C
      state machine consulting the tables: its body is a handful of
      one-hot selects over R0 entries instead of bucket draws, so the
      serial chain is ~D_outer + D_leaf draw rounds, not
      numrep*(depth+retries) draws.
    * GENERIC (everything else): one flat while_loop whose every
      iteration performs exactly one bucket draw; descent levels,
      retry-descents and chooseleaf recursion are a mode register, not
      nested loops.  Under vmap all lanes advance together, so
      wall-clock per batch is the maximum lane's total draw count.
    """
    plans = _plan_groups(cm, ruleno, result_max, spec_boost)
    total_tries, descend_once, vary_r_t, stable_t = cm.tunables
    NONE = jnp.int32(CRUSH_ITEM_NONE)
    UNDEF = jnp.int32(CRUSH_ITEM_UNDEF)
    OUTER, LEAF = jnp.int32(0), jnp.int32(1)

    HIP = jax.lax.Precision.HIGHEST
    SZ, NB = cm.sz, cm.nb
    NEGB = cm.bidx_f.shape[0]
    P = cm.arg_positions
    TN = max(cm.tree_nodes, 1)

    def rule_fn(x, weightv, row_pack, args_pack, tree_pack):
        # -- primitives closing over the operand tables ----------------

        def _lookup(i, n, table):
            """One-hot matmul lookup: table row i (f32-exact), the
            TPU-native replacement for a dynamic gather."""
            oh = (jnp.arange(n) == i).astype(jnp.float32)
            return jnp.matmul(oh, table, precision=HIP)

        def load_bucket(bidx_row):
            """One row_pack lookup ->
            (ids, wf, strawf, sumf, size, alg, bid)."""
            row = _lookup(bidx_row, NB, row_pack)
            ids = jnp.round(row[:SZ]).astype(jnp.int32)

            def f64pair(base):
                return row[base : base + SZ].astype(
                    jnp.float64
                ) * 65536.0 + row[base + SZ : base + 2 * SZ].astype(
                    jnp.float64
                )

            wf = f64pair(SZ)
            strawf = f64pair(3 * SZ)
            sumf = f64pair(5 * SZ)
            size = jnp.round(row[7 * SZ]).astype(jnp.int32)
            alg = jnp.round(row[7 * SZ + 1]).astype(jnp.int32)
            bid = jnp.round(row[7 * SZ + 2]).astype(jnp.int32)
            return ids, wf, strawf, sumf, size, alg, bid

        def straw2_draw(hash_ids, ids, wf, size, x, r, szv):
            """One straw2 draw-argmax (mapper.c:361-384) over item
            vectors of length ``szv`` (the full map width for the
            generic path, the level's max bucket size for the fast
            path's specialized draw rounds).

            ``hash_ids`` feed the hash (choose_args may remap them,
            bucket_straw2_choose mapper.c:363-384); the returned item
            is always from the bucket's real ``ids``.

            draw_i = -floor(L_i/w_i) computed in float64: L < 2^48 and
            w < 2^32 are f64-exact, the quotient estimate is off by at
            most one ulp, and a multiply-compare fixup restores the
            exact floor (q*w <= L < (q+1)*w with q*w < 2^53 exact)."""
            u = (
                _hash3(
                    jnp.uint32(x),
                    hash_ids.astype(jnp.uint32),
                    jnp.uint32(r),
                )
                & jnp.uint32(0xFFFF)
            )
            with jax.named_scope("crush_ln"):
                L = float(1 << 48) - _crush_ln_f64(
                    u, cm.ln_tbl1, cm.ln_tbl2
                )
            q0 = jnp.floor(L / jnp.where(wf > 0, wf, 1.0))
            t = q0 * wf
            q = (
                q0
                + (t + wf <= L).astype(jnp.float64)
                - (t > L).astype(jnp.float64)
            )
            draw = jnp.where(
                (wf > 0) & (jnp.arange(szv) < size), -q, -jnp.inf
            )
            am = jnp.argmax(draw)
            return jnp.sum(
                jnp.where(jnp.arange(szv) == am, ids, 0)
            ).astype(jnp.int32)

        def perm_draw(ids, size, bid, x, r, szv, uszv):
            """Uniform bucket chooser: slot r%size of the Fisher-Yates
            permutation seeded by hash(x, id, step)
            (bucket_perm_choose, mapper.c:73-131 — the r=0 fast path is
            the p=0 step of the same construction, so one loop covers
            both)."""
            size1 = jnp.maximum(size, 1)
            pr = jnp.int32(r) % size1
            # uniform buckets never exceed uszv (the uniform max of
            # the map, or of the level for specialized draws), so the
            # FY loop and slot vector are bounded by it, not the
            # map-wide max bucket size (a wide straw2 root would
            # otherwise make every draw quadratic in szv)
            usz = max(uszv, 1)
            slots = jnp.arange(usz, dtype=jnp.int32)

            def body(p, perm):
                p = jnp.int32(p)
                active = (p <= pr) & (p < size - 1)
                h = _hash3(
                    jnp.uint32(x), jnp.uint32(bid), jnp.uint32(p)
                )
                # C reduces the unsigned hash; an int32 view would flip
                # high hashes negative and change the residue
                i = (
                    h.astype(jnp.int64)
                    % jnp.maximum(size1 - p, 1).astype(jnp.int64)
                ).astype(jnp.int32)
                idx2 = p + i
                vp = jnp.sum(jnp.where(slots == p, perm, 0))
                v2 = jnp.sum(jnp.where(slots == idx2, perm, 0))
                swapped = jnp.where(
                    slots == p, v2, jnp.where(slots == idx2, vp, perm)
                )
                return jnp.where(active, swapped, perm).astype(
                    jnp.int32
                )

            perm = lax.fori_loop(0, usz, body, slots)
            s = jnp.sum(jnp.where(slots == pr, perm, 0))
            return jnp.sum(
                jnp.where(jnp.arange(szv) == s, ids, 0)
            ).astype(jnp.int32)

        def load_args(bidx_row, pos):
            """choose_args row for a bucket: position-selected straw2
            weights + hash-id remap (both equal the bucket's own tables
            for argless buckets, so one code path serves every map)."""
            arow = _lookup(bidx_row, NB, args_pack)
            poh = (
                jnp.arange(P) == jnp.clip(pos, 0, P - 1)
            ).astype(jnp.float32)
            hi = jnp.matmul(
                poh, arow[: P * SZ].reshape(P, SZ), precision=HIP
            )
            lo = jnp.matmul(
                poh,
                arow[P * SZ : 2 * P * SZ].reshape(P, SZ),
                precision=HIP,
            )
            awf = hi.astype(jnp.float64) * 65536.0 + lo.astype(
                jnp.float64
            )
            aids = jnp.round(arow[2 * P * SZ :]).astype(jnp.int32)
            return aids, awf

        def straw_draw(ids, strawf, size, x, r, szv):
            """Legacy straw chooser (bucket_straw_choose,
            mapper.c:227-245): draw_i = (hash3(x, item, r) & 0xffff) *
            straw_i, argmax with first-max-wins ties.  u16 * u32 < 2^48
            is f64-exact."""
            u = (
                _hash3(
                    jnp.uint32(x),
                    ids.astype(jnp.uint32),
                    jnp.uint32(r),
                )
                & jnp.uint32(0xFFFF)
            ).astype(jnp.float64)
            draw = jnp.where(
                jnp.arange(szv) < size, u * strawf, -jnp.inf
            )
            am = jnp.argmax(draw)  # first max, like the C's strict >
            return jnp.sum(
                jnp.where(jnp.arange(szv) == am, ids, 0)
            ).astype(jnp.int32)

        def list_draw(ids, wf, sumf, size, bid, x, r, szv):
            """List chooser (bucket_list_choose, mapper.c:141-164):
            walk tail→head, item i wins when
            (hash4(x, item, r, bucket_id) & 0xffff) * sum_i >> 16 <
            weight_i — i.e. the HIGHEST accepting index wins; items[0]
            when nobody accepts.  u16 * u32 < 2^48 and the >>16 floor
            are f64-exact."""
            w = (
                _hash4(
                    jnp.uint32(x),
                    ids.astype(jnp.uint32),
                    jnp.uint32(r),
                    bid.astype(jnp.uint32),
                )
                & jnp.uint32(0xFFFF)
            ).astype(jnp.float64)
            scaled = jnp.floor(w * sumf / 65536.0)
            accept = (scaled < wf) & (jnp.arange(szv) < size)
            idx = jnp.max(jnp.where(accept, jnp.arange(szv), -1))
            win = jnp.maximum(idx, 0)  # items[0] when none accept
            return jnp.sum(
                jnp.where(jnp.arange(szv) == win, ids, 0)
            ).astype(jnp.int32)

        def tree_draw(trow, ids, bid, x, r, szv):
            """Tree chooser (bucket_tree_choose, mapper.c:195-222):
            weighted descent of the implicit binary tree over an
            already-loaded node-weight row.  The C's
            (hash32_4 * u64 weight) >> 32 exceeds f64's 2^53 exact
            range, so it is computed as split integer arithmetic: with
            hash = h1*2^16 + h0 and A = h1*w = a1*2^16 + a0,
            t = a1 + floor((a0*2^16 + h0*w) / 2^32) — every
            intermediate stays below 2^49."""
            nwf = trow[:TN].astype(jnp.float64) * 65536.0 + trow[
                TN : 2 * TN
            ].astype(jnp.float64)
            start = jnp.round(trow[2 * TN]).astype(jnp.int32)

            def node_w(n):
                oh = (jnp.arange(TN) == n).astype(jnp.float64)
                return jnp.sum(oh * nwf)

            def body(_i, n):
                frozen = (n & 1) == 1
                w = node_w(n)
                hv = _hash4(
                    jnp.uint32(x),
                    n.astype(jnp.uint32),
                    jnp.uint32(r),
                    bid.astype(jnp.uint32),
                ).astype(jnp.float64)
                h1 = jnp.floor(hv / 65536.0)
                h0 = hv - h1 * 65536.0
                A = h1 * w
                a1 = jnp.floor(A / 65536.0)
                a0 = A - a1 * 65536.0
                t = a1 + jnp.floor(
                    (a0 * 65536.0 + h0 * w) / 4294967296.0
                )
                low = (n & -n) >> 1  # 2^(height-1)
                left = n - low
                nxt = jnp.where(t < node_w(left), left, n + low)
                return jnp.where(frozen, n, nxt).astype(jnp.int32)

            depth = max(TN.bit_length(), 1)
            n = lax.fori_loop(0, depth, body, start)
            slot = n >> 1
            return jnp.sum(
                jnp.where(jnp.arange(szv) == slot, ids, 0)
            ).astype(jnp.int32)

        def dispatch_draw(
            bidx_row, ids, wf, strawf, sumf, size, alg, bid, x, r, pos
        ):
            """crush_bucket_choose over already-loaded bucket data; the
            perm/straw/list/tree paths only compile into maps
            containing those bucket algs, the choose_args path only
            into maps that carry choose_args."""
            if args_pack is not None:
                hash_ids, awf = load_args(bidx_row, pos)
            else:
                hash_ids, awf = ids, wf
            item = straw2_draw(hash_ids, ids, awf, size, x, r, SZ)
            if cm.has_uniform:
                uni = perm_draw(
                    ids, size, bid, x, r, SZ, cm.uniform_sz
                )
                item = jnp.where(
                    alg == CRUSH_BUCKET_UNIFORM, uni, item
                )
            if cm.has_straw:
                st = straw_draw(ids, strawf, size, x, r, SZ)
                item = jnp.where(alg == CRUSH_BUCKET_STRAW, st, item)
            if cm.has_list:
                li = list_draw(ids, wf, sumf, size, bid, x, r, SZ)
                item = jnp.where(alg == CRUSH_BUCKET_LIST, li, item)
            if cm.has_tree:
                trow = _lookup(bidx_row, NB, tree_pack)
                tr = tree_draw(trow, ids, bid, x, r, SZ)
                item = jnp.where(alg == CRUSH_BUCKET_TREE, tr, item)
            return item

        def bucket_draw(bidx_row, x, r, pos):
            """Load + draw; returns (item, bucket_size)."""
            ids, wf, strawf, sumf, size, alg, bid = load_bucket(
                bidx_row
            )
            return (
                dispatch_draw(
                    bidx_row, ids, wf, strawf, sumf, size, alg, bid,
                    x, r, pos,
                ),
                size,
            )

        def row_of(item):
            """Bucket row for a (negative) item; -1 if invalid."""
            neg = -1 - item
            ok = (item < 0) & (neg < NEGB)
            row = jnp.round(
                _lookup(jnp.clip(neg, 0, None), NEGB, cm.bidx_f)
            ).astype(jnp.int32)
            return jnp.where(ok, row, -1)

        def type_of_row(nrow):
            return jnp.round(
                _lookup(jnp.maximum(nrow, 0), NB, cm.types_f)
            ).astype(jnp.int32)

        def is_out(weightv, item, x):
            """mapper.c:424-438 over the device reweight vector."""
            w = weightv[jnp.clip(item, 0, weightv.shape[0] - 1)]
            oob = item >= weightv.shape[0]
            hashed = (
                _hash2(jnp.uint32(x), jnp.uint32(item)).astype(
                    jnp.int32
                )
                & 0xFFFF
            )
            return oob | (w == 0) | ((w < 0x10000) & (hashed >= w))

        def classify(item, target_type):
            """(found, descend, hard_bad, nrow) for a drawn item
            against the level's target type (the firstn/indep descent
            checks)."""
            nrow = row_of(item)
            is_dev = item >= 0
            invalid = (~is_dev) & (nrow < 0)
            bad_dev = item >= cm.max_devices
            itype = jnp.where(is_dev, 0, type_of_row(nrow))
            found = (~bad_dev) & (~invalid) & (itype == target_type)
            hard_bad = (
                bad_dev | invalid | (is_dev & (itype != target_type))
            )
            descend = (~found) & (~hard_bad)
            return found, descend, hard_bad, nrow

        # -- fast firstn: speculative tables + table-driven machine ----

        def make_step_drawer(sinfo):
            """Specialized draw for one descent level of the fast
            path: the one-hot runs over the level's REACHABLE bucket
            set (often a single row — then no lookup at all) and item
            vectors shrink to the level's max bucket size, instead of
            the map-wide NB x SZ tables the generic path must assume.
            Returns draw(cur_row, r) -> (item, size)."""
            rows_t = sinfo["rows"]
            NS = len(rows_t)
            SZi = min(sinfo["sz"], SZ)
            algs = set(sinfo["algs"])
            idxv = jnp.asarray(rows_t, dtype=jnp.int32)

            # static gathers on the operand packs: computed once per
            # call over (NS, cols) — not per lane
            sub = row_pack[idxv, :]
            pieces = [
                sub[:, 0:SZi],
                sub[:, SZ : SZ + SZi],
                sub[:, 2 * SZ : 2 * SZ + SZi],
            ]
            ncol = 3 * SZi
            off_straw = off_sum = None
            if CRUSH_BUCKET_STRAW in algs:
                off_straw = ncol
                pieces += [
                    sub[:, 3 * SZ : 3 * SZ + SZi],
                    sub[:, 4 * SZ : 4 * SZ + SZi],
                ]
                ncol += 2 * SZi
            if CRUSH_BUCKET_LIST in algs:
                off_sum = ncol
                pieces += [
                    sub[:, 5 * SZ : 5 * SZ + SZi],
                    sub[:, 6 * SZ : 6 * SZ + SZi],
                ]
                ncol += 2 * SZi
            off_meta = ncol
            pieces.append(sub[:, 7 * SZ : 7 * SZ + 3])
            tab = jnp.concatenate(pieces, axis=1)
            if args_pack is not None:
                asub = args_pack[idxv, :]
                atab = jnp.concatenate(
                    [
                        asub[:, 0:SZi],
                        asub[:, SZ : SZ + SZi],
                        asub[:, 2 * SZ : 2 * SZ + SZi],
                    ],
                    axis=1,
                )
            if CRUSH_BUCKET_TREE in algs:
                ttab = tree_pack[idxv, :]

            def f64cols(row, a, b):
                return row[a:b].astype(jnp.float64) * 65536.0 + row[
                    a + SZi : b + SZi
                ].astype(jnp.float64)

            def draw(cur_row, r):
                if NS == 1:
                    row = tab[0]
                else:
                    oh = (idxv == cur_row).astype(jnp.float32)
                    row = jnp.matmul(oh, tab, precision=HIP)
                ids = jnp.round(row[0:SZi]).astype(jnp.int32)
                wf = f64cols(row, SZi, 2 * SZi)
                size = jnp.round(row[off_meta]).astype(jnp.int32)
                alg = jnp.round(row[off_meta + 1]).astype(jnp.int32)
                bid = jnp.round(row[off_meta + 2]).astype(jnp.int32)
                if args_pack is not None:
                    if NS == 1:
                        arow = atab[0]
                    else:
                        arow = jnp.matmul(oh, atab, precision=HIP)
                    # atab layout: aw_hi | aw_lo | aids
                    hash_ids = jnp.round(
                        arow[2 * SZi : 3 * SZi]
                    ).astype(jnp.int32)
                    awf = f64cols(arow, 0, SZi)
                else:
                    hash_ids, awf = ids, wf
                item = straw2_draw(
                    hash_ids, ids, awf, size, x, r, SZi
                )
                if CRUSH_BUCKET_UNIFORM in algs:
                    uni = perm_draw(
                        ids, size, bid, x, r, SZi, sinfo["usz"]
                    )
                    item = jnp.where(
                        alg == CRUSH_BUCKET_UNIFORM, uni, item
                    )
                if CRUSH_BUCKET_STRAW in algs:
                    strawf = f64cols(
                        row, off_straw, off_straw + SZi
                    )
                    st = straw_draw(ids, strawf, size, x, r, SZi)
                    item = jnp.where(
                        alg == CRUSH_BUCKET_STRAW, st, item
                    )
                if CRUSH_BUCKET_LIST in algs:
                    sumf = f64cols(row, off_sum, off_sum + SZi)
                    li = list_draw(
                        ids, wf, sumf, size, bid, x, r, SZi
                    )
                    item = jnp.where(
                        alg == CRUSH_BUCKET_LIST, li, item
                    )
                if CRUSH_BUCKET_TREE in algs:
                    if NS == 1:
                        trow = ttab[0]
                    else:
                        trow = jnp.matmul(oh, ttab, precision=HIP)
                    tr = tree_draw(trow, ids, bid, x, r, SZi)
                    item = jnp.where(
                        alg == CRUSH_BUCKET_TREE, tr, item
                    )
                return item, size

            return draw

        def spec_descend(steps, rows, rs, valids, target, scope):
            """Batched candidate descents: each candidate draws with
            its own fixed r at every level (the crush_choose_firstn
            contract), one specialized draw round per level; returns
            (kind, item) per candidate.  Level ``d``'s draw round is
            named ``<scope>_d<d>`` on the device (depth from the
            descent's start: root, rack, host for the outer one)."""
            kinds = jnp.where(
                valids, jnp.int32(_K_OVER), jnp.int32(_K_BAD)
            )
            items = jnp.full(rows.shape, NONE)
            tt = jnp.int32(target)
            for depth, sinfo in enumerate(steps):
                drawer = make_step_drawer(sinfo)

                def one(row, r, kind, prev_it):
                    it, bsize = drawer(row, r)
                    empty = bsize == 0
                    found, desc, hard_bad, nrow = classify(it, tt)
                    active = kind == _K_OVER
                    nk = jnp.where(
                        active,
                        jnp.where(
                            empty,
                            _K_RETRY,
                            jnp.where(
                                found,
                                _K_FOUND,
                                jnp.where(hard_bad, _K_BAD, _K_OVER),
                            ),
                        ),
                        kind,
                    ).astype(jnp.int32)
                    nit = jnp.where(active, it, prev_it)
                    nrow2 = jnp.where(
                        active & desc & ~empty, nrow, row
                    ).astype(jnp.int32)
                    return nrow2, nk, nit

                with jax.named_scope(f"{scope}_d{depth}"):
                    rows, kinds, items = jax.vmap(one)(
                        rows, rs, kinds, items
                    )
            return kinds, items

        def fast_firstn(plan, weightv):
            f = plan["fast"]
            R0 = f["R0"]
            ttype = plan["ttype"]
            numrep, nslots = plan["numrep"], plan["nslots"]
            tries, leaf_tries = plan["tries"], plan["leaf_tries"]
            vary_r, stable = plan["vary_r"], plan["stable"]
            leaf = plan["leaf"]
            R = nslots
            rvec = jnp.arange(R0, dtype=jnp.int32)

            rows0 = jnp.full((R0,), jnp.int32(plan["take_row"]))
            kinds, items = spec_descend(
                f["outer_steps"], rows0, rvec,
                jnp.full((R0,), True), ttype, "crush_draw_outer",
            )
            if ttype == 0:
                with jax.named_scope("crush_is_out"):
                    oisout = jax.vmap(
                        lambda it: is_out(weightv, it, x)
                    )(items) & (kinds == _K_FOUND)
            else:
                oisout = jnp.zeros((R0,), bool)

            if leaf:
                L0, Pd = f["L0"], f["Pd"]
                start_rows = jax.vmap(row_of)(items)
                lvalid = (kinds == _K_FOUND) & (items < 0)
                if vary_r:
                    sub_r = rvec >> (vary_r - 1)
                else:
                    sub_r = jnp.zeros_like(rvec)
                reps = Pd * L0
                sub_flat = jnp.repeat(sub_r, reps)
                rows_flat = jnp.repeat(start_rows, reps)
                valid_flat = jnp.repeat(lvalid, reps)
                pos_flat = jnp.tile(
                    jnp.repeat(
                        jnp.arange(Pd, dtype=jnp.int32), L0
                    ),
                    R0,
                )
                l_flat = jnp.tile(
                    jnp.arange(L0, dtype=jnp.int32), R0 * Pd
                )
                leaf_rep = (
                    jnp.zeros_like(pos_flat) if stable else pos_flat
                )
                rleaf_flat = leaf_rep + sub_flat + l_flat
                with jax.named_scope("crush_leaf_descent"):
                    lkinds, litems = spec_descend(
                        f["leaf_steps"], rows_flat, rleaf_flat,
                        valid_flat, 0, "crush_draw_leaf",
                    )
                    with jax.named_scope("crush_is_out"):
                        lisout = jax.vmap(
                            lambda it: is_out(weightv, it, x)
                        )(litems) & (lkinds == _K_FOUND)

            def cond(st):
                return ~st[0]

            def body(st):
                (done, okf, rep, outpos, ftotal, lftotal, mode,
                 dom_r, domain, out, out2) = st
                in_leaf = mode == LEAF
                r = rep + ftotal
                over_r = (~in_leaf) & (r >= R0)
                ohr = jnp.arange(R0) == jnp.clip(r, 0, R0 - 1)
                k = jnp.sum(jnp.where(ohr, kinds, 0)).astype(
                    jnp.int32
                )
                it = jnp.sum(jnp.where(ohr, items, 0)).astype(
                    jnp.int32
                )
                o = (~in_leaf) & ~over_r
                o_found = o & (k == _K_FOUND)
                o_bad = o & (k == _K_BAD)
                o_retry = o & (k == _K_RETRY)
                o_over = (~in_leaf) & (over_r | (k == _K_OVER))

                collide = o_found & jnp.any(
                    (jnp.arange(R) < outpos) & (out == it)
                )
                if leaf:
                    enter_leaf = o_found & ~collide & (it < 0)
                    direct = o_found & ~collide & (it >= 0)
                else:
                    enter_leaf = jnp.bool_(False)
                    direct = o_found & ~collide
                if ttype == 0:
                    oio = jnp.any(ohr & oisout)
                    direct_out = direct & oio
                else:
                    direct_out = jnp.bool_(False)
                place_direct = direct & ~direct_out

                if leaf:
                    l = in_leaf
                    l_over_idx = lftotal >= f["L0"]
                    if stable:
                        pos_comp = jnp.int32(0)
                    else:
                        pos_comp = jnp.clip(outpos, 0, f["Pd"] - 1)
                    fidx = (
                        dom_r * (f["Pd"] * f["L0"])
                        + pos_comp * f["L0"]
                        + jnp.clip(lftotal, 0, f["L0"] - 1)
                    )
                    ohl = jnp.arange(R0 * f["Pd"] * f["L0"]) == fidx
                    lk = jnp.sum(jnp.where(ohl, lkinds, 0)).astype(
                        jnp.int32
                    )
                    lit = jnp.sum(jnp.where(ohl, litems, 0)).astype(
                        jnp.int32
                    )
                    lio = jnp.any(ohl & lisout)
                    lc = l & ~l_over_idx
                    l_found = lc & (lk == _K_FOUND)
                    l_bad = lc & (lk == _K_BAD)
                    l_empty = lc & (lk == _K_RETRY)
                    l_over = l & (l_over_idx | (lk == _K_OVER))
                    l_rej = l_found & (
                        jnp.any(
                            (jnp.arange(R) < outpos) & (out2 == lit)
                        )
                        | lio
                    )
                    l_place = l_found & ~l_rej
                    l_retry_cand = l_empty | l_rej
                    l_exhaust = l_retry_cand & (
                        lftotal + 1 >= leaf_tries
                    )
                    l_retry = l_retry_cand & ~l_exhaust
                else:
                    lit = NONE
                    l_bad = l_exhaust = l_retry = l_place = (
                        jnp.bool_(False)
                    )
                    l_over = jnp.bool_(False)

                outer_reject = (
                    o_retry | collide | direct_out | l_bad | l_exhaust
                )
                or_skip = outer_reject & (ftotal + 1 >= tries)
                or_retry = outer_reject & ~or_skip
                place = place_direct | l_place
                skip = o_bad | or_skip
                advance = place | skip
                fail = o_over | l_over

                sel = place & (jnp.arange(R) == outpos)
                out = jnp.where(
                    sel, jnp.where(l_place, domain, it), out
                )
                if leaf:
                    out2 = jnp.where(sel, lit, out2)

                new_rep = rep + advance
                new_outpos = (outpos + place).astype(jnp.int32)
                new_ftotal = jnp.where(
                    advance, 0, jnp.where(or_retry, ftotal + 1, ftotal)
                ).astype(jnp.int32)
                new_lftotal = jnp.where(
                    enter_leaf,
                    0,
                    jnp.where(l_retry, lftotal + 1, lftotal),
                ).astype(jnp.int32)
                stay_leaf = enter_leaf | l_retry
                new_mode = jnp.where(stay_leaf, LEAF, OUTER)
                new_dom_r = jnp.where(enter_leaf, r, dom_r).astype(
                    jnp.int32
                )
                new_domain = jnp.where(enter_leaf, it, domain).astype(
                    jnp.int32
                )
                new_ok = okf & ~fail
                new_done = (
                    done
                    | fail
                    | (new_rep >= numrep)
                    | (new_outpos >= nslots)
                )
                return (
                    new_done, new_ok, new_rep.astype(jnp.int32),
                    new_outpos, new_ftotal, new_lftotal, new_mode,
                    new_dom_r, new_domain, out, out2,
                )

            init = (
                jnp.bool_(numrep <= 0 or R == 0),
                jnp.bool_(True),
                jnp.int32(0), jnp.int32(0), jnp.int32(0),
                jnp.int32(0), OUTER, jnp.int32(0), jnp.int32(0),
                jnp.full((R,), NONE, dtype=jnp.int32),
                jnp.full((R,), NONE, dtype=jnp.int32),
            )
            # the table-driven replay of the C state machine:
            # collisions, is_out rejects, retries
            with jax.named_scope("crush_collide_reject"):
                st = lax.while_loop(cond, body, init)
            okf, outpos = st[1], st[3]
            out, out2 = st[9], st[10]
            return (out2 if leaf else out), outpos, okf

        # -- generic choosers (one draw per while_loop iteration) ------

        def choose_firstn(plan, weightv):
            """crush_choose_firstn (mapper.c:460-648) as a state
            machine.

            Registers: rep/outpos/ftotal track the C loop variables;
            mode switches between the outer descent (toward ttype) and
            the chooseleaf descent (toward a device under ``domain``);
            every reject path advances r' exactly as the C does.
            Exception to one-draw-per-iteration: empty-bucket and
            depth-exceeded transitions consume an iteration without
            using the draw.

            ``numrep`` is the C loop bound (reps keep advancing past
            skipped replicas); ``nslots`` is the count bound on actual
            placements (the C's out_size/count)."""
            take_row = plan["take_row"]
            ttype = plan["ttype"]
            numrep, nslots = plan["numrep"], plan["nslots"]
            tries, leaf_tries = plan["tries"], plan["leaf_tries"]
            vary_r, stable = plan["vary_r"], plan["stable"]
            leaf = plan["leaf"]
            R = nslots

            def cond(st):
                return ~st[0]

            def body(st):
                (done, rep, outpos, ftotal, mode, cur_row, domain,
                 lftotal, depth, out, out2) = st
                in_leaf = mode == LEAF
                leaf_rep = jnp.int32(0) if stable else outpos
                r_outer = rep + ftotal
                if vary_r:
                    sub_r = r_outer >> (vary_r - 1)
                else:
                    sub_r = jnp.int32(0)
                r = jnp.where(
                    in_leaf, leaf_rep + sub_r + lftotal, r_outer
                )

                # choose_args position: the C passes the running outpos
                # at every firstn draw (mapper.c:526-530), and the
                # chooseleaf recursion re-enters with the same outpos
                # (:578-588), so one register serves both modes
                item, bsize = bucket_draw(cur_row, x, r, outpos)
                empty = bsize == 0
                target = jnp.where(in_leaf, 0, jnp.int32(ttype))
                found, desc, hard_bad, nrow = classify(item, target)
                # depth guard: runaway descent behaves like a bad item
                too_deep = desc & (depth + 1 >= MAX_DEPTH)
                hard_bad = (~empty) & (hard_bad | too_deep)
                desc = (~empty) & desc & ~too_deep
                found = (~empty) & found

                o = ~in_leaf
                o_desc = o & desc
                o_bad = o & hard_bad
                o_found = o & found
                collide = o_found & jnp.any(
                    (jnp.arange(R) < outpos) & (out == item)
                )
                if leaf:
                    enter_leaf = o_found & ~collide & (item < 0)
                    direct = o_found & ~collide & (item >= 0)
                else:
                    enter_leaf = jnp.bool_(False)
                    direct = o_found & ~collide
                if ttype == 0:
                    direct_out = direct & is_out(weightv, item, x)
                else:
                    direct_out = jnp.bool_(False)
                place_direct = direct & ~direct_out

                l = in_leaf
                l_desc = l & desc
                l_bad = l & hard_bad
                l_found = l & found
                l_rej = l_found & (
                    jnp.any(
                        (jnp.arange(R) < outpos) & (out2 == item)
                    )
                    | is_out(weightv, item, x)
                )
                l_place = l_found & ~l_rej
                l_retry_cand = (l & empty) | l_rej
                l_exhaust = l_retry_cand & (
                    lftotal + 1 >= leaf_tries
                )
                l_retry = l_retry_cand & ~l_exhaust

                outer_reject = (
                    (o & empty)
                    | collide
                    | direct_out
                    | l_bad
                    | l_exhaust
                )
                or_skip = outer_reject & (ftotal + 1 >= tries)
                or_retry = outer_reject & ~or_skip

                place = place_direct | l_place
                skip = o_bad | or_skip
                advance = place | skip

                sel = place & (jnp.arange(R) == outpos)
                out = jnp.where(
                    sel, jnp.where(l_place, domain, item), out
                )
                if leaf:
                    out2 = jnp.where(sel, item, out2)

                new_rep = rep + advance
                new_outpos_i = outpos + place
                new_done = done | (new_rep >= numrep) | (
                    new_outpos_i >= nslots
                )
                new_outpos = new_outpos_i
                new_ftotal = jnp.where(
                    advance, 0, jnp.where(or_retry, ftotal + 1, ftotal)
                )
                new_lftotal = jnp.where(
                    enter_leaf,
                    0,
                    jnp.where(l_retry, lftotal + 1, lftotal),
                )
                stay_leaf = enter_leaf | l_desc | l_retry
                new_mode = jnp.where(stay_leaf, LEAF, OUTER)
                new_row = jnp.where(
                    o_desc | l_desc | enter_leaf,
                    nrow,
                    jnp.where(l_retry, row_of(domain), take_row),
                )
                new_domain = jnp.where(enter_leaf, item, domain)
                new_depth = jnp.where(o_desc | l_desc, depth + 1, 0)
                return (
                    new_done, new_rep, new_outpos.astype(jnp.int32),
                    new_ftotal.astype(jnp.int32), new_mode, new_row,
                    new_domain, new_lftotal.astype(jnp.int32),
                    new_depth.astype(jnp.int32), out, out2,
                )

            init = (
                jnp.bool_(numrep <= 0 or R == 0), jnp.int32(0),
                jnp.int32(0), jnp.int32(0),
                OUTER, jnp.int32(take_row), jnp.int32(0),
                jnp.int32(0), jnp.int32(0),
                jnp.full((R,), NONE, dtype=jnp.int32),
                jnp.full((R,), NONE, dtype=jnp.int32),
            )
            with jax.named_scope("crush_choose_firstn"):
                st = lax.while_loop(cond, body, init)
            outpos = st[2]
            out, out2 = st[9], st[10]
            return (out2 if leaf else out), outpos, jnp.bool_(True)

        def choose_indep(plan, weightv):
            """crush_choose_indep (mapper.c:655-843) as a state
            machine.

            ``slot`` scans the UNDEF positions of each round; finishing
            a slot jumps straight to the next UNDEF one, and exhausting
            them advances the round (ftotal).  r' = slot + n*ftotal at
            the outer level and slot + r_outer + n*lftotal inside
            chooseleaf, exactly the C advancement.  ``numrep`` is the
            unclamped replica count — it sets the r' stride even when
            left0 < numrep."""
            take_row = plan["take_row"]
            ttype = plan["ttype"]
            numrep, nslots = plan["numrep"], plan["nslots"]
            tries, leaf_tries = plan["tries"], plan["leaf_tries"]
            leaf = plan["leaf"]
            left0 = nslots
            R = left0

            def slot_advance(out, slot, left, ftotal):
                """Next UNDEF slot after ``slot``; wrap advances the
                round."""
                undef = out == UNDEF
                after = undef & (jnp.arange(R) > slot)
                has_after = jnp.any(after)
                nxt = jnp.where(
                    has_after, jnp.argmax(after), jnp.argmax(undef)
                ).astype(jnp.int32)
                new_ftotal = ftotal + jnp.where(has_after, 0, 1)
                done = (
                    (left <= 0)
                    | (~jnp.any(undef))
                    | (new_ftotal >= tries)
                )
                return nxt, new_ftotal, done

            def cond(st):
                return ~st[0]

            def body(st):
                (done, slot, left, ftotal, mode, cur_row, domain,
                 lftotal, depth, parent_r, out, out2) = st
                in_leaf = mode == LEAF
                ids, wf, strawf, sumf, bsize, alg, bid = load_bucket(
                    cur_row
                )
                # uniform buckets whose size divides numrep advance r
                # with stride numrep+1 (mapper.c:722-728) — per descent
                # level
                if cm.has_uniform:
                    stride = jnp.where(
                        (alg == CRUSH_BUCKET_UNIFORM)
                        & (bsize > 0)
                        & (bsize % numrep == 0),
                        numrep + 1,
                        numrep,
                    )
                else:
                    stride = jnp.int32(numrep)
                # parent_r freezes the outer r at domain-choice time
                # for the chooseleaf recursion (its nested call
                # re-bases on it)
                r = jnp.where(
                    in_leaf,
                    slot + parent_r + stride * lftotal,
                    slot + stride * ftotal,
                )

                # choose_args position: indep outer draws pass the
                # FRAME outpos — constant 0 from do_rule
                # (mapper.c:736-739) — and the leaf recursion enters
                # with outpos=rep (:790-794), so leaf draws use the
                # slot index
                pos = jnp.where(in_leaf, slot, jnp.int32(0))
                item = dispatch_draw(
                    cur_row, ids, wf, strawf, sumf, bsize, alg, bid,
                    x, r, pos,
                )
                empty = bsize == 0
                target = jnp.where(in_leaf, 0, jnp.int32(ttype))
                found, desc, hard_bad, nrow = classify(item, target)
                too_deep = desc & (depth + 1 >= MAX_DEPTH)
                hard_bad = (~empty) & (hard_bad | too_deep)
                desc = (~empty) & desc & ~too_deep
                found = (~empty) & found

                o = ~in_leaf
                o_desc = o & desc
                o_kill = o & hard_bad  # slot permanently NONE
                o_found = o & found
                collide = o_found & jnp.any(out == item)
                if leaf:
                    enter_leaf = o_found & ~collide & (item < 0)
                    direct = o_found & ~collide & (item >= 0)
                else:
                    enter_leaf = jnp.bool_(False)
                    direct = o_found & ~collide
                if ttype == 0:
                    direct_out = direct & is_out(weightv, item, x)
                else:
                    direct_out = jnp.bool_(False)
                place_direct = direct & ~direct_out

                l = in_leaf
                l_desc = l & desc
                l_fail_now = l & hard_bad  # inner NONE -> outer break
                l_found = l & found
                l_rej = l_found & is_out(weightv, item, x)
                l_place = l_found & ~l_rej
                l_retry_cand = (l & empty) | l_rej
                l_exhaust = l_retry_cand & (
                    lftotal + 1 >= leaf_tries
                )
                l_retry = l_retry_cand & ~l_exhaust

                place = place_direct | l_place
                kill = o_kill
                # break: slot stays UNDEF for a later round
                brk = (
                    (o & empty)
                    | collide
                    | direct_out
                    | l_fail_now
                    | l_exhaust
                )

                sel = jnp.arange(R) == slot
                out = jnp.where(
                    sel & place,
                    jnp.where(l_place, domain, item),
                    jnp.where(sel & kill, NONE, out),
                )
                if leaf:
                    out2 = jnp.where(
                        sel & place,
                        item,
                        jnp.where(sel & kill, NONE, out2),
                    )
                new_left = left - (place | kill).astype(jnp.int32)

                finished = place | kill | brk
                nxt, adv_ftotal, adv_done = slot_advance(
                    out, slot, new_left, ftotal
                )
                new_slot = jnp.where(finished, nxt, slot)
                new_ftotal = jnp.where(finished, adv_ftotal, ftotal)
                new_done = done | (finished & adv_done)

                stay_leaf = enter_leaf | l_desc | l_retry
                new_mode = jnp.where(
                    stay_leaf & ~finished, LEAF, OUTER
                )
                new_row = jnp.where(
                    o_desc | l_desc | enter_leaf,
                    nrow,
                    jnp.where(
                        l_retry & ~finished,
                        row_of(domain),
                        take_row,
                    ),
                )
                new_domain = jnp.where(enter_leaf, item, domain)
                new_lftotal = jnp.where(
                    enter_leaf,
                    0,
                    jnp.where(l_retry, lftotal + 1, lftotal),
                )
                new_depth = jnp.where(o_desc | l_desc, depth + 1, 0)
                new_parent_r = jnp.where(enter_leaf, r, parent_r)
                return (
                    new_done, new_slot, new_left,
                    new_ftotal.astype(jnp.int32), new_mode, new_row,
                    new_domain, new_lftotal.astype(jnp.int32),
                    new_depth.astype(jnp.int32),
                    new_parent_r.astype(jnp.int32), out, out2,
                )

            init = (
                jnp.bool_(R == 0) | jnp.bool_(tries <= 0),
                jnp.int32(0), jnp.int32(R), jnp.int32(0),
                OUTER, jnp.int32(take_row), jnp.int32(0),
                jnp.int32(0), jnp.int32(0), jnp.int32(0),
                jnp.full((R,), UNDEF, dtype=jnp.int32),
                jnp.full((R,), UNDEF, dtype=jnp.int32),
            )
            with jax.named_scope("crush_choose_indep"):
                st = lax.while_loop(cond, body, init)
            out, out2 = st[10], st[11]
            out = jnp.where(out == UNDEF, NONE, out)
            out2 = jnp.where(out2 == UNDEF, NONE, out2)
            return (out2 if leaf else out), jnp.int32(R), jnp.bool_(
                True
            )

        # -- the rule program ------------------------------------------
        result = jnp.full((result_max,), NONE, dtype=jnp.int32)
        rlen = jnp.int32(0)
        okall = jnp.bool_(True)
        for plan in plans:
            if plan["fast"] is not None:
                got, n, okg = fast_firstn(plan, weightv)
            elif plan["firstn"]:
                got, n, okg = choose_firstn(plan, weightv)
            else:
                got, n, okg = choose_indep(plan, weightv)
            okall = okall & okg
            # append got[:n] to result at rlen
            for i in range(plan["nslots"]):
                slot = rlen + i
                valid = (i < n) & (slot < result_max)
                result = jnp.where(
                    valid & (jnp.arange(result_max) == slot),
                    got[i],
                    result,
                )
            rlen = jnp.minimum(rlen + n, result_max)
        return result, rlen, okall

    return rule_fn


# Kernel cache keyed on map STRUCTURE (CompiledMap.skey), not the
# CompiledMap instance: recompiling the same topology with new weights
# (the per-epoch mon/mgr pattern) reuses the jitted program and pays
# only a host→device table upload.  Bounded LRU: a long-lived daemon
# recompiling across structural epochs must not pin every old
# topology's executable (and its closed-over CompiledMap) forever.
_KERNEL_CACHE: collections.OrderedDict = collections.OrderedDict()
_KERNEL_CACHE_MAX = 64


def _kernel_cache_get(key):
    fn = _KERNEL_CACHE.get(key)
    if fn is not None:
        _KERNEL_CACHE.move_to_end(key)
    return fn


def _kernel_cache_put(key, fn):
    _KERNEL_CACHE[key] = fn
    while len(_KERNEL_CACHE) > _KERNEL_CACHE_MAX:
        _KERNEL_CACHE.popitem(last=False)


def _unpack_tables(has_args, has_tree, packs):
    """Positional operand unpacking shared by every jitted wrapper
    (the operand list omits absent args/tree packs)."""
    i = 0
    args_pack = tree_pack = None
    if has_args:
        args_pack = packs[i]
        i += 1
    if has_tree:
        tree_pack = packs[i]
    return args_pack, tree_pack


def _kernel_tables(cm: CompiledMap):
    t = [cm.row_pack]
    if cm.args_pack is not None:
        t.append(cm.args_pack)
    if cm.tree_pack is not None:
        t.append(cm.tree_pack)
    return t


def _batched(
    cm: CompiledMap, ruleno: int, result_max: int, spec_boost: int = 0
):
    key = ("xs", cm.skey, ruleno, result_max, spec_boost)
    fn = _kernel_cache_get(key)
    if fn is None:
        rf = _make_rule_fn(cm, ruleno, result_max, spec_boost)
        has_args = cm.args_pack is not None
        has_tree = cm.tree_pack is not None

        def call(xs, wv, row_pack, *packs):
            args_pack, tree_pack = _unpack_tables(
                has_args, has_tree, packs
            )
            return jax.vmap(
                lambda x: rf(x, wv, row_pack, args_pack, tree_pack)
            )(xs)

        fn = jax.jit(call)
        _kernel_cache_put(key, fn)
    return fn


def _batched_range(
    cm: CompiledMap,
    ruleno: int,
    result_max: int,
    n: int,
    packed: bool = False,
    spec_boost: int = 0,
):
    """Jitted contiguous-range variant: xs = lo + iota(n) is built ON
    DEVICE, so a bulk remap (osdmaptool --test-map-pgs shape) ships
    one scalar per call instead of an N-element host array, and calls
    pipeline without host round-trips between dispatches.  With
    ``packed`` the results ship as int16 (-32768 encodes NONE) and
    counts as uint8 — half the device→host bytes on a bulk remap."""
    key = ("rg", cm.skey, ruleno, result_max, n, packed, spec_boost)
    fn = _kernel_cache_get(key)
    if fn is None:
        rf = _make_rule_fn(cm, ruleno, result_max, spec_boost)
        has_args = cm.args_pack is not None
        has_tree = cm.tree_pack is not None

        def call(lo, wv, row_pack, *packs):
            args_pack, tree_pack = _unpack_tables(
                has_args, has_tree, packs
            )
            xs = lo + jnp.arange(n, dtype=jnp.int32)
            res, counts, ok = jax.vmap(
                lambda x: rf(x, wv, row_pack, args_pack, tree_pack)
            )(xs)
            if packed:
                res = jnp.where(
                    res == CRUSH_ITEM_NONE, jnp.int32(-32768), res
                ).astype(jnp.int16)
                counts = counts.astype(jnp.uint8)
            return res, counts, ok

        fn = jax.jit(call)
        _kernel_cache_put(key, fn)
    return fn


def oracle_fallback(
    cm: CompiledMap, ruleno: int, result_max: int, weights=None
):
    """``finish(xs, res, counts, ok) -> (results, counts)`` for one
    remap's parts: re-maps the lanes whose speculative retry window
    overflowed (ok == False) through the exact host oracle and returns
    finalized numpy.  No-op (and no copy) on a part whose every lane is
    ok — the common case for any realistically-sized map.  What is fixed
    a remap (the mutation check, the weight list) is made once, at the
    first part that holds a bad lane.  Accepts the packed int16 wire
    form (see _batched_range) and unpacks it."""

    @functools.cache
    def weight_list():
        if getattr(cm.source, "mutation", 0) != cm.source_mutation:
            raise RuntimeError(
                "CrushMap mutated since compile_map(): the oracle "
                "fallback would mix old-snapshot kernel results with "
                "new-map lanes — recompile the map first"
            )
        if weights is None:
            return [0x10000] * max(cm.max_devices, 1)
        return np.asarray(weights).tolist()

    def finish(xs, res, counts, ok):
        with tracing.span("crush_fallback") as span:
            res = np.asarray(res)
            counts = np.asarray(counts)
            if res.dtype == np.int16:
                res32 = res.astype(np.int32)
                res32[res == -32768] = CRUSH_ITEM_NONE
                res = res32
                counts = counts.astype(np.int32)
            ok = np.asarray(ok)
            bad = () if ok.all() else np.nonzero(~ok)[0]
            span.set_tag("lanes", len(bad))
            if len(bad):
                from ..ops.kernel_stats import kernel_stats

                ks = kernel_stats()
                ks.perf.inc(
                    ks.counter(
                        "crush", "fallback_lanes",
                        desc="lanes re-mapped by the host oracle",
                    ),
                    len(bad),
                )
                wl = weight_list()
                res = res.copy()
                counts = counts.copy()
                xs = np.asarray(xs)
                for i in bad:
                    row = cm.source.do_rule(
                        ruleno, int(xs[i]), result_max, wl
                    )
                    res[i, :] = CRUSH_ITEM_NONE
                    res[i, : len(row)] = row
                    counts[i] = len(row)
            return res, counts

    return finish


def apply_oracle_fallback(
    cm: CompiledMap,
    ruleno: int,
    xs,
    res,
    counts,
    ok,
    result_max: int,
    weights=None,
):
    """:func:`oracle_fallback` for a result that is finished in one
    piece: returns finalized numpy (results, counts)."""
    return oracle_fallback(cm, ruleno, result_max, weights)(
        xs, res, counts, ok
    )


def _spec_boost_for(weights) -> int:
    """1 when the reweight vector meaningfully deviates from full-in
    (is_out() rejects then drive extra retries the topology-sized
    speculation window cannot predict), else 0."""
    if weights is None:
        return 0
    w = np.asarray(weights)
    if w.size == 0:
        return 0
    frac = np.count_nonzero(w != 0x10000) / w.size
    return 1 if frac > 0.02 else 0


def batched_rule_call(cm: CompiledMap, ruleno: int, result_max: int,
                      weights):
    """The jitted batched kernel plus its packed table operands —
    the dispatch seam mesh-sharded callers (osd/sharded_mapping.py)
    go through so they never re-implement table packing or the
    speculation-boost selection.  Returns ``(fn, tables)``; call as
    ``fn(xs_dev, weight_vector, *tables)`` with ``xs_dev`` placed
    under any sharding (the kernel is lane-independent) and get the
    raw ``(res, counts, ok)`` device arrays back — finalize with
    :func:`apply_oracle_fallback`."""
    fn = _batched(cm, ruleno, result_max, _spec_boost_for(weights))
    return fn, _kernel_tables(cm)


# Lanes per dispatched program.  The kernel's scratch grows with the
# lane count: compiled for a v5e on the BASELINE #5 map (10,000 OSDs,
# rule 0, 3 replicas) memory_analysis() reports temp_size_in_bytes of
# 1,653,150,720 at 2^16 lanes (~25 kB a lane; 3.4 GB at 2^17, 7.0 GB at
# 2^18) next to <1 MB of tables and 1.4 MB of results.
CHUNK_LANES = 1 << 16

# Parts issued beyond the one the host is working on (map_parts).  The
# device's queue must not run empty while the host finishes its slowest
# part.  On that map (PERF.md section 6, PR 37, one v5e) a part is 26 ms
# of device and the host's share of one — its inputs, the fallback
# scan, the widening, the fix-ups of osd/mapping.py — 19-23 ms, or some
# 60 where the part holds a lane for the scalar oracle (38-40 ms a
# lane): more than two parts of device, less than three.  Measured in
# that cell: 1 ahead 2.05 M mappings/s and the device idle 19.6%, 2
# ahead 2.19 M / 15.6%, 3 ahead 2.18 M / 15.2%, 4 ahead 2.22 M / 13.9%
# (runs 2% apart of themselves).  Three, with the part in hand, is
# four programs' scratch at most: 6.6 GB of a v5e's 16 GB.
PARTS_AHEAD = 3


def _stage(name: str):
    """The flight recorder's ``compute`` / ``sync`` stage (and so its
    dev_compute / dev_sync span) of the dispatch record open on this
    thread now, where there is one."""
    from ..ops.profiler import current_dispatch

    record = current_dispatch()
    if record is None:
        return contextlib.nullcontext()
    return record.stage(name)


def map_parts(dispatch, xs, chunk: int | None = None):
    """Run ``dispatch(xs_part) -> (res, counts, ok)`` (non-blocking
    device arrays, at least ``len(xs_part)`` rows each) over ``xs`` in
    parts of at most ``chunk`` lanes (default CHUNK_LANES) and yield
    each part ``(lo, res, counts, ok)`` as numpy, its own rows only, as
    soon as it is fetched — with the parts after it, up to PARTS_AHEAD
    of them, already issued, so what the caller does with a part runs
    under their kernels.  A batch larger than ``chunk`` is cut into
    parts of exactly ``chunk`` lanes (the tail repeats its last input
    and the extra rows are dropped), so one compiled program is
    replayed.  A batch of one part has nothing issued ahead.

    Each part is issued and fetched under the compute / sync stage of
    the dispatch record open on the calling thread at that moment: the
    fetch (np.asarray) is where the host waits for the device.  The
    time between a part's hand-over and the caller's return for the
    next is the host's work on it: ``l_tpu_crush_host_ns``, and
    ``l_tpu_crush_host_overlapped_ns`` for the share spent with a later
    part issued and not yet fetched."""
    from ..ops.kernel_stats import kernel_stats

    chunk = chunk or CHUNK_LANES
    n = len(xs)
    ks = kernel_stats()
    host_ns = ks.counter(
        "crush", "host_ns",
        desc="host time on fetched parts of a batch (ns)",
    )
    overlapped_ns = ks.counter(
        "crush", "host_overlapped_ns",
        desc="host_ns spent with a later part on the device (ns)",
    )
    starts = iter(range(0, max(n, 1), chunk))
    issued = collections.deque()

    def issue_next():
        lo = next(starts, None)
        if lo is None:
            return
        part = xs[lo : lo + chunk]
        short = chunk - len(part) if n > chunk else 0
        if short:
            part = np.concatenate(
                [np.asarray(part), np.full(short, part[-1], part.dtype)]
            )
        with _stage("compute"):
            issued.append((lo, dispatch(part)))

    def fetch():
        lo, arrays = issued.popleft()
        rows = min(chunk, n - lo)
        with _stage("sync"):
            return (lo, *(np.asarray(a)[:rows] for a in arrays))

    for _ in range(PARTS_AHEAD):
        issue_next()
    while True:
        issue_next()
        if not issued:
            return
        part = fetch()
        t0 = time.perf_counter_ns()
        try:
            yield part
        finally:
            dt = time.perf_counter_ns() - t0
            ks.perf.inc(host_ns, dt)
            if issued:
                ks.perf.inc(overlapped_ns, dt)


def join_parts(parts) -> tuple:
    """The arrays of ``(lo, array, ...)`` parts joined row-wise, in
    order; a single part's own arrays, uncopied."""
    parts = list(parts)
    if len(parts) == 1:
        return parts[0][1:]
    return tuple(
        np.concatenate([p[i] for p in parts])
        for i in range(1, len(parts[0]))
    )


def map_chunked(dispatch, xs, chunk: int | None = None):
    """:func:`map_parts` joined: the three results as numpy,
    ``len(xs)`` rows each."""
    return join_parts(map_parts(dispatch, xs, chunk))


def rule_parts(
    cm: CompiledMap,
    ruleno: int,
    xs,
    result_max: int,
    weights=None,
    place=None,
    chunk: int | None = None,
):
    """``batch_do_rule`` a part at a time: sets the kernel up now (an
    UnsupportedMap is raised here, before anything is issued) and
    returns a generator of finished parts ``(lo, results, counts)`` —
    :func:`map_parts` with each part's fallback lanes re-mapped as it
    is handed out, under the kernels of the parts after it.  ``place``
    puts a part's inputs on the device(s) (default ``jnp.asarray``;
    the mesh path shards them) and ``chunk`` is the lanes a part.
    ``xs`` is only ever sliced a part at a time (``xs[lo:hi]``, as the
    part is issued and again as it is finished) and asked its length,
    so it may work its values out as they are wanted."""
    if weights is None:
        weights = np.full(max(cm.max_devices, 1), 0x10000, np.int32)
    # by way of numpy: jnp.asarray walks a list an element at a time
    wv = jnp.asarray(np.asarray(weights, dtype=np.int32))
    fn, tables = batched_rule_call(cm, ruleno, result_max, weights)
    place = place or jnp.asarray
    finish = oracle_fallback(cm, ruleno, result_max, weights)

    def dispatch(part):
        return fn(place(np.asarray(part, dtype=np.int32)), wv, *tables)

    def parts():
        raw = map_parts(dispatch, xs, chunk)
        with contextlib.closing(raw):
            for lo, res, counts, ok in raw:
                yield (
                    lo,
                    *finish(xs[lo : lo + len(ok)], res, counts, ok),
                )

    return parts()


def batch_do_rule(
    cm: CompiledMap,
    ruleno: int,
    xs,
    result_max: int,
    weights=None,
):
    """Map a batch of inputs: xs (N,) -> (results (N, result_max) int32
    padded with CRUSH_ITEM_NONE, counts (N,)) as numpy arrays.
    ``weights`` is the 16.16 device reweight vector.  Batches beyond
    CHUNK_LANES run as replays of one CHUNK_LANES-lane program."""
    if weights is None:
        weights = np.full(max(cm.max_devices, 1), 0x10000, np.int32)
    xs = np.asarray(xs, dtype=np.int32)
    wv = jnp.asarray(weights, dtype=jnp.int32)
    fn = _batched(cm, ruleno, result_max, _spec_boost_for(weights))
    tables = _kernel_tables(cm)
    res, counts, ok = map_chunked(
        lambda part: fn(jnp.asarray(part), wv, *tables), xs
    )
    return apply_oracle_fallback(
        cm, ruleno, xs, res, counts, ok, result_max, weights
    )


def batch_do_rule_range(
    cm: CompiledMap,
    ruleno: int,
    lo: int,
    n: int,
    result_max: int,
    weights=None,
    packed: bool = False,
):
    """Map the contiguous inputs [lo, lo+n): like ``batch_do_rule``
    but the input range materializes on device and the call returns
    WITHOUT blocking — callers overlap dispatch with host-side
    materialization of earlier results, then finish each chunk with
    ``apply_oracle_fallback(cm, ruleno, np.arange(lo, lo+n), *chunk,
    result_max, weights)``.  Returns (results, counts, ok) as device
    arrays.  ``packed`` ships results as int16/uint8 (halving the
    device→host bytes; apply_oracle_fallback unpacks) and requires
    every id magnitude < 32768."""
    if weights is None:
        weights = np.full(max(cm.max_devices, 1), 0x10000, np.int32)
    if packed and (
        cm.max_devices >= 32768
        or len(cm.bidx) >= 32768
        or result_max > 255
    ):
        packed = False  # ids/counts wouldn't fit the packed wire form
    wv = jnp.asarray(weights, dtype=jnp.int32)
    return _batched_range(
        cm, ruleno, result_max, n, packed, _spec_boost_for(weights)
    )(jnp.int32(lo), wv, *_kernel_tables(cm))


def make_chained_runner(
    cm: CompiledMap,
    ruleno: int,
    result_max: int,
    n: int,
    iters: int = 8,
    weights=None,
):
    """Benchmark harness: one jitted program that maps ``iters``
    consecutive n-PG ranges back-to-back ON DEVICE, consuming each
    round's results into a checksum that seeds the next round's input
    offset (so no round can be elided or overlapped away).  Returns
    ``run(lo) -> int`` which blocks until all iters*n mappings
    completed; wall-time / (iters*n) is the kernel's device-resident
    mapping rate with dispatch and host-transfer costs excluded."""
    if weights is None:
        weights = np.full(max(cm.max_devices, 1), 0x10000, np.int32)
    wv = jnp.asarray(weights, dtype=jnp.int32)
    key = ("chain", cm.skey, ruleno, result_max, n, iters)
    fn = _kernel_cache_get(key)
    if fn is None:
        rf = _make_rule_fn(cm, ruleno, result_max)
        has_args = cm.args_pack is not None
        has_tree = cm.tree_pack is not None

        def call(lo, wv, row_pack, *packs):
            args_pack, tree_pack = _unpack_tables(
                has_args, has_tree, packs
            )

            def body(i, acc):
                xs = (
                    lo
                    + acc % 7
                    + i * n
                    + jnp.arange(n, dtype=jnp.int32)
                )
                res, cnt, ok = jax.vmap(
                    lambda x: rf(
                        x, wv, row_pack, args_pack, tree_pack
                    )
                )(xs)
                return (
                    acc
                    + jnp.sum(res, dtype=jnp.int32)
                    + jnp.sum(cnt, dtype=jnp.int32)
                    + jnp.sum(ok, dtype=jnp.int32)
                ).astype(jnp.int32)

            return lax.fori_loop(0, iters, body, jnp.int32(0))

        fn = jax.jit(call)
        _kernel_cache_put(key, fn)

    tables = _kernel_tables(cm)

    def run(lo: int) -> int:
        return int(fn(jnp.int32(lo), wv, *tables))

    return run
