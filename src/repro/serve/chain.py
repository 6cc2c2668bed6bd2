"""Fused scan–join chain math for small query batches (the serving fast path).

The general ``repro.serve.exec`` pipeline compiles a whole algebra tree
and amortizes its per-dispatch constant over thousands of same-signature
queries; at batch 1–64 that constant (operand marshalling, a ~30-leaf
pytree, one device→host sync per capacity counter) dominates.  This
module implements the dominant plan shapes — a ``Scan`` followed by up
to two inner ``BindJoin`` s under the standard ``Project → Sort →
Limit`` tail — as ONE fused unit with a deliberately tiny calling
convention:

* per reader: the packed split keys, the three index columns, and the
  primary-term row starts (all persistent store arrays);
* per query: one ``(n_readers, 3)`` int32 constants row, a validity
  flag, and a limit — packed into a single ``[batch, qrow_width]``
  matrix so each dispatch pays exactly one host→device transfer;
* out: the projected/sorted/limited binding columns, the row counts,
  and a single ``[n_stages]`` *max-needed* vector — one tiny transfer
  replaces the general path's per-capacity ``needed`` dict sync.

The chain math (:func:`chain_query`) is written once in pure jnp;
:func:`make_batched` ``vmap`` s it over the batch and the caller jits
the result.  XLA compiles that formulation on every backend, so it is
the one fast path.

All semantics match the general executor operator for operator: the
same packed-bound encoding (``-1`` wildcard packs below every real id,
``-2`` unknown constants produce empty ranges), the same seeded
primary-term bisection, the same packed cumsum/searchsorted bind-join
expansion, and the same stable full-column sort — so the fast path is
row-for-row identical to the general pipeline (property-tested).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

I32_MAX = np.int32(np.iinfo(np.int32).max)
UNBOUND = np.int32(-1)


# ---------------------------------------------------------------------------
# packed split-key binary search (the canonical definitions; the general
# executor re-exports these)
# ---------------------------------------------------------------------------


def pack_bound(q0, q1, q2, bits: int):
    """Pack a (possibly wildcarded) query bound into the store's split
    63-bit key space (see ``TripleStore.device_keys``): fields are shifted
    +1 so ``-1`` packs below every real id and ``I32_MAX`` clamps to the
    all-ones field above every id.  Returns int32 ``(hi, lo)`` with the
    low word sign-bit-biased, matching the store's key columns."""

    def f(x):
        # clip BEFORE the +1: I32_MAX + 1 would wrap in int32
        return jnp.clip(
            jnp.asarray(x), -1, (1 << bits) - 2
        ).astype(jnp.uint32) + jnp.uint32(1)

    f0, f1, f2 = f(q0), f(q1), f(q2)
    hi = (f0 << (2 * bits - 32)) | (f1 >> (32 - bits))
    lo = ((f1 & jnp.uint32((1 << (32 - bits)) - 1)) << bits) | f2
    return (
        hi.astype(jnp.int32),
        jax.lax.bitcast_convert_type(lo ^ jnp.uint32(0x80000000), jnp.int32),
    )


def lex_search2(khi, klo, qhi, qlo, upper: bool, rounds: int,
                lo_init=None, hi_init=None):
    """Binary search on the split-key pair: count of rows lex-< (or <= for
    ``upper``) the query bound.  ``rounds`` covers the widest possible
    [lo_init, hi_init) window (the full store by default; a seeded search
    passes a primary-term row range and correspondingly few rounds)."""
    n = khi.shape[0]
    if lo_init is None:
        lo_i = jnp.zeros(jnp.shape(qhi), jnp.int32)
        hi_i = jnp.full(jnp.shape(qhi), n, jnp.int32)
    else:
        lo_i = jnp.broadcast_to(lo_init, jnp.shape(qhi))
        hi_i = jnp.broadcast_to(hi_init, jnp.shape(qhi))

    def body(_, state):
        lo_i, hi_i = state
        mid = lo_i + ((hi_i - lo_i) >> 1)
        g = jnp.clip(mid, 0, max(n - 1, 0))
        mhi, mlo = khi[g], klo[g]
        tail = (mlo <= qlo) if upper else (mlo < qlo)
        before = (mhi < qhi) | ((mhi == qhi) & tail)
        open_ = lo_i < hi_i
        return (
            jnp.where(open_ & before, mid + 1, lo_i),
            jnp.where(open_ & ~before, mid, hi_i),
        )

    lo_i, _ = jax.lax.fori_loop(0, rounds, body, (lo_i, hi_i))
    return lo_i


# ---------------------------------------------------------------------------
# the static chain description
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ReaderSpec:
    """One pattern reader, resolved to its index order: ``src[j]`` says
    where index-order position ``j`` s bound comes from — ``('c', pos)``
    a constant from the reader's consts row, ``('b', col)`` a chain
    binding column, ``('w', 0)`` wildcard — and ``out`` lists the
    wildcard positions that bind new chain columns."""

    src: tuple[tuple[str, int], tuple[str, int], tuple[str, int]]
    out: tuple[tuple[int, int], ...]       # (index-order pos j, chain col)
    prim_rounds: int                       # seeded-bisection rounds


@dataclasses.dataclass(frozen=True)
class ChainSpec:
    """The whole fused chain: readers in pipeline order (reader ``i``
    reads constants row ``i``), the chain column count, and the
    projection ``out_cols`` (chain column per output variable, ``-1``
    for a selected variable no pattern ever binds)."""

    readers: tuple[ReaderSpec, ...]
    n_cols: int
    out_cols: tuple[int, ...]
    key_bits: int
    rounds: int                            # full-store bisection rounds
    store_n: int                           # base rows (>= 1)


def _reader_range(spec: ChainSpec, r: ReaderSpec, khi, klo, prim_start,
                  lo_q, hi_q, primary_q):
    """(start, end) rows inside the reader's bound range; seeded to the
    primary term's row range when the primary is bound."""
    qhi_l, qlo_l = pack_bound(*lo_q, spec.key_bits)
    qhi_h, qlo_h = pack_bound(*hi_q, spec.key_bits)
    if primary_q is None:
        lo = lex_search2(khi, klo, qhi_l, qlo_l, False, spec.rounds)
        hi = lex_search2(khi, klo, qhi_h, qlo_h, True, spec.rounds)
        return lo, hi
    T = prim_start.shape[0] - 1
    g0 = jnp.clip(primary_q, 0, max(T - 1, 0))
    lo0 = prim_start[g0]
    hi0 = prim_start[g0 + 1]
    lo = lex_search2(khi, klo, qhi_l, qlo_l, False, r.prim_rounds, lo0, hi0)
    hi = lex_search2(khi, klo, qhi_h, qlo_h, True, r.prim_rounds, lo0, hi0)
    # a negative primary (unknown constant / padded row / unmatched left
    # binding) is an empty range
    ok = primary_q >= 0
    zero = jnp.zeros_like(lo)
    return jnp.where(ok, lo, zero), jnp.where(ok, hi, zero)


def _bounds(r: ReaderSpec, consts_r, cols, shape):
    """The reader's (lo, hi) bound triples in index order, plus the
    primary operand (None = wildcard primary, full-store search)."""
    lo_q, hi_q = [], []
    for kind, arg in r.src:
        if kind == "c":
            v = jnp.broadcast_to(consts_r[arg], shape)
            lo_q.append(v)
            hi_q.append(v)
        elif kind == "b":
            v = cols[arg]
            lo_q.append(v)
            hi_q.append(v)
        else:
            lo_q.append(jnp.broadcast_to(jnp.int32(-1), shape))
            hi_q.append(jnp.broadcast_to(I32_MAX, shape))
    kind, arg = r.src[0]
    if kind == "c":
        primary_q = jnp.broadcast_to(consts_r[arg], shape)
    elif kind == "b":
        primary_q = cols[arg]
    else:
        primary_q = None
    return lo_q, hi_q, primary_q


# ---------------------------------------------------------------------------
# one query's whole chain (pure jnp)
# ---------------------------------------------------------------------------


def chain_query(
    spec: ChainSpec,
    caps: tuple[int, ...],
    operands: tuple,
    consts_q,       # int32[n_readers, 3]
    qvalid_q,       # bool scalar (False for batch-pad rows)
    qlimit_q,       # int32 scalar, -1 = no limit
):
    """Run the fused chain for one query.  ``operands[i]`` is reader
    ``i``'s ``(khi, klo, c0, c1, c2, prim_start)``; ``caps[i]`` its
    output capacity.  Returns ``(out_cols, n, needed)`` where ``needed``
    is the exact per-stage row requirement (the capacity feedback)."""
    cols: list = [None] * spec.n_cols

    r0 = spec.readers[0]
    khi, klo, c0, c1, c2, prim = operands[0]
    lo_q, hi_q, primary_q = _bounds(r0, consts_q[0], cols, ())
    lo, hi = _reader_range(spec, r0, khi, klo, prim, lo_q, hi_q, primary_q)
    count = jnp.where(qvalid_q, hi - lo, 0)
    needed = [count]
    cap = caps[0]
    r = jnp.clip(lo + jnp.arange(cap, dtype=jnp.int32), 0, spec.store_n - 1)
    valid = jnp.arange(cap) < count
    by_j = (c0, c1, c2)
    for j, col in r0.out:
        cols[col] = jnp.where(valid, by_j[j][r], UNBOUND)
    n = jnp.minimum(count, cap)

    for k in range(1, len(spec.readers)):
        rk = spec.readers[k]
        khi, klo, c0, c1, c2, prim = operands[k]
        cl = caps[k - 1]
        lo_q, hi_q, primary_q = _bounds(rk, consts_q[k], cols, (cl,))
        lo, hi = _reader_range(
            spec, rk, khi, klo, prim, lo_q, hi_q, primary_q
        )
        cnt = jnp.where(jnp.arange(cl) < n, hi - lo, 0)
        # packed expansion (same as the general executor): out row j
        # belongs to the left row whose count prefix-sum passes j
        cum = jnp.cumsum(cnt)
        total = cum[cl - 1]
        cap = caps[k]
        j = jnp.arange(cap, dtype=jnp.int32)
        rowidx = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
        rowc = jnp.clip(rowidx, 0, cl - 1)
        prev = jnp.where(rowc > 0, cum[rowc - 1], 0)
        kk = j - prev
        rr = jnp.clip(lo[rowc] + kk, 0, spec.store_n - 1)
        valid_out = j < jnp.minimum(total, cap)
        new_cols: list = [None] * spec.n_cols
        for col in range(spec.n_cols):
            if cols[col] is not None:
                new_cols[col] = jnp.where(
                    valid_out, cols[col][rowc], UNBOUND
                )
        by_j = (c0, c1, c2)
        for jj, col in rk.out:
            new_cols[col] = jnp.where(valid_out, by_j[jj][rr], UNBOUND)
        cols = new_cols
        needed.append(total)
        n = jnp.minimum(total, cap)

    # tail: Project -> Sort -> Limit, exactly the general pipeline's.
    # Sorting by EVERY output column makes the table a pure function of
    # the row multiset, so the direct variadic key sort reproduces the
    # general path's permutation sort row for row.
    cap = caps[-1]
    outs = []
    for col in spec.out_cols:
        if col >= 0 and cols[col] is not None:
            outs.append(cols[col])
        else:
            outs.append(jnp.full(cap, UNBOUND, jnp.int32))
    valid = jnp.arange(cap) < n
    if outs:
        keys = tuple(jnp.where(valid, c, I32_MAX) for c in outs)
        sorted_cols = jax.lax.sort(keys, num_keys=len(keys), is_stable=True)
        outs = [jnp.where(valid, c, UNBOUND) for c in sorted_cols]
    n = jnp.where(qlimit_q >= 0, jnp.minimum(n, qlimit_q), n)
    return tuple(outs), n, jnp.stack(needed)


# ---------------------------------------------------------------------------
# the batched entry point
# ---------------------------------------------------------------------------


def qrow_width(n_readers: int) -> int:
    """Width of the packed per-query row: the flattened ``(n_readers, 3)``
    constants, the validity flag, and the limit.  One int32 matrix is the
    fast path's ENTIRE per-dispatch transfer — one host→device put
    instead of three (the generic device-put machinery, not the copy,
    is the batch-1 cost)."""
    return 3 * n_readers + 2


def _split_args(spec: ChainSpec, args):
    n_ops = 6 * len(spec.readers)
    operands = tuple(args[6 * i : 6 * i + 6] for i in range(len(spec.readers)))
    return operands, args[n_ops]


def _unpack_qrow(spec: ChainSpec, qrow):
    """Split one packed per-query row into (consts[R, 3], valid, limit)."""
    R = len(spec.readers)
    return qrow[: 3 * R].reshape(R, 3), qrow[3 * R] != 0, qrow[3 * R + 1]


def make_batched(spec: ChainSpec, caps: tuple[int, ...]):
    """A jit-able batched entry point for one (chain, capacities) shape.

    Takes the flat argument list ``(*reader operands, qbuf[B,
    qrow_width])`` — the packed per-query rows, see :func:`qrow_width` —
    and returns ``(out_cols, n, needed_max)`` with ``needed_max``
    reduced over the batch on device — the caller syncs ONE tiny vector
    to drive capacity feedback."""

    def batched(*args):
        operands, qbuf = _split_args(spec, args)

        def single(qrow):
            consts_q, qvalid_q, qlimit_q = _unpack_qrow(spec, qrow)
            return chain_query(
                spec, caps, operands, consts_q, qvalid_q, qlimit_q
            )

        outs, n, needed = jax.vmap(single)(qbuf)
        return outs, n, jnp.max(needed, axis=0)

    return batched
