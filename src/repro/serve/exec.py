"""Jitted plan executor — a whole query (batch) in one fused dispatch.

The old BGP path materialized every binding table on host between joins;
here the full operator tree — range scans, sorted-merge joins, OPTIONAL
backfill, UNION concat, filters, group/count, distinct/sort/order/limit —
lowers to *one* jitted function.  Binding tables stay on device as
power-of-two padded int32 columns with a packed-valid-prefix row count;
``-1`` is the unbound sentinel a ``LeftJoin`` (or a partial ``UNION``
arm) backfills for maybe-unbound variables.

Shapes must be static under jit, so every operator has a *capacity* (scan
rows, join fan-out ``M``, join output rows, union/backfill concat rows).
Capacities start from the planner's estimates and are corrected by a
feedback loop: the compiled pipeline returns, alongside the results, the
*exact* size each point needed; if anything was truncated the executor
re-runs once with capacities bumped to ``next_pow2(needed)`` (growth is
monotone, so the loop terminates; capacities are remembered per query
signature, so a serving workload converges to exactly one dispatch per
batch).  Power-of-two padding everywhere bounds the number of distinct
compiled shapes to O(log n) per signature.

The plan is a DAG, not a tree — UNION arms share the required subtree and
an OPTIONAL bind-join chain shares its tagged left side — so node
evaluation is memoized per trace: shared work is computed once per
dispatch.  GROUP BY counts with a device segment-sum over the key-sorted
table; ORDER BY sorts by the store's value-typed ``order_rank`` side
table (count columns by their integer value) with a term-id tie-break.

Batching: the single-query pipeline is ``vmap``-ed over the batch axis, so
*many same-shape queries execute per dispatch* — constants (term ids, rank
bounds) are the only per-query data.  This is the server's hot path.
"""

from __future__ import annotations

import dataclasses
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashset import next_pow2
from repro.kg.query import _lex_search
from repro.kg.store import ORDERS, TripleStore
from repro.obs import get_registry, get_tracer
from repro.serve import algebra as A
from repro.serve import fastpath as FP
from repro.serve import plan as P
from repro.serve.values import value_table

I32_MAX = np.int32(np.iinfo(np.int32).max)
UNBOUND = np.int32(-1)
_MAX_GROW_ROUNDS = 12
_FP_UNSET = object()  # fast-path cache sentinel (None = ineligible plan)


def plan_label(sig: tuple) -> str:
    """A short, process-stable label for a plan signature — what dispatch
    spans and per-signature latency histograms are tagged with (the raw
    signature tuple is too bulky for a metric name)."""
    return f"{zlib.crc32(repr(sig).encode('utf-8')) & 0xFFFFFFFF:08x}"


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BatchResult:
    """Padded, deterministically ordered solution tables for a whole batch."""

    store: TripleStore
    vars: tuple[str, ...]
    cols: dict[str, np.ndarray]   # int32[B, C] each (C >= max count)
    counts: np.ndarray            # int64[B]
    # aggregate output columns (COUNT aliases): their cells are plain
    # integers, not term ids — ``rows`` returns them as ints
    agg_vars: tuple[str, ...] = ()

    def n(self, i: int) -> int:
        return int(self.counts[i])

    def ids(self, i: int) -> list[tuple[int, ...]]:
        """Query ``i``'s rows as raw int tuples (term ids, -1 = unbound;
        counts stay counts)."""
        k = self.n(i)
        return [
            tuple(int(self.cols[v][i, r]) for v in self.vars) for r in range(k)
        ]

    def rows(self, i: int, limit: int | None = None) -> list[tuple]:
        """Query ``i``'s rows decoded to rendered terms (None = unbound);
        aggregate columns come back as plain ints."""
        k = self.n(i)
        if limit is not None:
            k = min(k, limit)
        out = []
        for r in range(k):
            row = []
            for v in self.vars:
                x = int(self.cols[v][i, r])
                if v in self.agg_vars:
                    row.append(x)
                elif x < 0:
                    row.append(None)
                else:
                    row.append(self.store.decode_term(x))
            out.append(tuple(row))
        return out


# ---------------------------------------------------------------------------
# traced operators (single query; vmapped over the batch by the compiler)
# ---------------------------------------------------------------------------


def _pack_bound(q0, q1, q2, bits: int):
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


def _lex_search2(khi, klo, qhi, qlo, upper: bool, rounds: int,
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


def _range_search(
    keys, c0, c1, c2, lo_q, hi_q, bits: int, rounds: int,
    primary_q=None, prim_start=None, prim_rounds: int | None = None,
):
    """(start, end) of the rows inside [lo_q, hi_q] — a 2-column split-key
    binary search when the store's ids fit the packed fields, else the
    general 3-column lexicographic search.  With a bound primary term
    (``primary_q``), the bisection is *seeded* to that term's row range
    (``prim_start``) and runs only ``prim_rounds`` rounds — for a bound
    subject that is the subject's degree, not the store size."""
    if keys is not None:
        khi, klo = keys
        qhi_l, qlo_l = _pack_bound(*lo_q, bits)
        qhi_h, qlo_h = _pack_bound(*hi_q, bits)
        if primary_q is not None:
            T = prim_start.shape[0] - 1
            g0 = jnp.clip(primary_q, 0, max(T - 1, 0))
            lo0 = prim_start[g0]
            hi0 = prim_start[g0 + 1]
            lo = _lex_search2(
                khi, klo, qhi_l, qlo_l, False, prim_rounds, lo0, hi0
            )
            hi = _lex_search2(
                khi, klo, qhi_h, qlo_h, True, prim_rounds, lo0, hi0
            )
            # a negative primary (unknown constant / padded row) is empty
            ok = primary_q >= 0
            zero = jnp.zeros_like(lo)
            return jnp.where(ok, lo, zero), jnp.where(ok, hi, zero)
        lo = _lex_search2(khi, klo, qhi_l, qlo_l, upper=False, rounds=rounds)
        hi = _lex_search2(khi, klo, qhi_h, qlo_h, upper=True, rounds=rounds)
        return lo, hi
    lo = _lex_search(c0, c1, c2, lo_q[0], lo_q[1], lo_q[2], upper=False)
    hi = _lex_search(c0, c1, c2, hi_q[0], hi_q[1], hi_q[2], upper=True)
    return lo, hi


def _compact(cols: dict, mask, cap: int):
    """Scatter masked rows to a packed prefix of a ``cap``-row table.
    Returns (cols, valid_count, total_wanted) — ``total_wanted`` feeds the
    capacity feedback when it exceeds ``cap``."""
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    keep = mask & (pos < cap)
    idx = jnp.where(keep, pos, cap)  # cap is out-of-range: dropped
    out = {
        v: jnp.full(cap, UNBOUND, jnp.int32).at[idx].set(c, mode="drop")
        for v, c in cols.items()
    }
    total = jnp.sum(mask.astype(jnp.int32))
    return out, jnp.minimum(total, cap), total


def _sort_perm(cols: dict, order: tuple[str, ...], n, cap: int):
    """Permutation sorting the valid prefix lexicographically by ``order``
    columns (invalid rows, keyed all-I32_MAX, sort last; real ids are far
    below it).  One variadic ``lax.sort`` pass over all key columns.  Term
    ids are dense ranks of rendered terms, so this order is identical
    across stores of the same graph."""
    valid = jnp.arange(cap) < n
    keys = [jnp.where(valid, cols[v], I32_MAX) for v in order]
    payload = jnp.arange(cap, dtype=jnp.int32)
    out = jax.lax.sort(
        tuple(keys) + (payload,), num_keys=len(keys), is_stable=True
    )
    return out[-1], valid


class _Lowerer:
    """Builds the traced single-query pipeline for one (plan, caps)."""

    def __init__(
        self,
        plan: P.Plan,
        caps: dict[str, int],
        store_n: int,
        key_bits: int,
        packed: bool,
        prim_rounds: dict[int, int] | None = None,
        order_is_tid: bool = False,
        overlay: bool = False,
        delta_cap: int = 1,
        delta_rounds: int = 1,
    ):
        self.plan = plan
        self.caps = caps
        self.store_n = store_n
        self.key_bits = key_bits
        self.packed = packed
        self.order_is_tid = order_is_tid
        self.rounds = max(1, int(store_n).bit_length())
        self.prim_rounds = prim_rounds or {}
        # live-overlay second scan arm (see repro.live.delta.OverlayView):
        # every reader range-scans the base index AND the re-sorted delta
        # index, rank-selects non-tombstoned base rows through per-order
        # alive prefix sums, and emits base matches then delta matches
        self.overlay = overlay
        self.delta_cap = delta_cap
        self.delta_rounds = delta_rounds
        self.scan_index = {s.node_id: i for i, s in enumerate(plan.scans)}
        self.needed: dict[str, jnp.ndarray] = {}
        # the column sequence each node's rows are known to be sorted by
        # (empty when unknown) — lets the tail skip redundant sorts
        self._sorted: dict[int, tuple[str, ...]] = {}
        # per-trace node memo: the plan is a DAG (shared union/optional
        # subtrees) and every shared node must be computed exactly once
        self._memo: dict[int, tuple] = {}
        # bound during trace
        self.scan_cols: dict[int, tuple] = {}
        self.scan_keys: dict[int, jnp.ndarray | None] = {}
        self.scan_prim: dict[int, jnp.ndarray | None] = {}
        self.dscan_cols: dict[int, tuple] = {}
        self.dscan_keys: dict[int, jnp.ndarray | None] = {}
        self.alive: dict[int, jnp.ndarray] = {}
        self.dn = None
        self.vt_arrays: tuple | None = None
        self.consts = None
        self.fops = None
        self.qvalid = None
        self.qlimit = None

    def _search_args(self, node):
        """Per-reader seeding operands (packed path only)."""
        if not self.packed:
            return {}
        return {
            "prim_start": self.scan_prim[node.node_id],
            "prim_rounds": self.prim_rounds[node.node_id],
        }

    # -- scans ---------------------------------------------------------------

    def _scan(self, node: P.Scan):
        cap = self.caps.get(f"scan{node.node_id}", 1)
        c0, c1, c2 = self.scan_cols[node.node_id]
        q = self.consts[self.scan_index[node.node_id]]
        perm3 = ORDERS[node.order]
        lo_q, hi_q = [], []
        for j in range(3):
            pos = perm3[j]
            if pos in node.const_slots:
                lo_q.append(q[pos])
                hi_q.append(q[pos])
            else:
                lo_q.append(jnp.int32(-1))
                hi_q.append(I32_MAX)
        primary_q = q[perm3[0]] if perm3[0] in node.const_slots else None
        lo, hi = _range_search(
            self.scan_keys[node.node_id], c0, c1, c2,
            lo_q, hi_q, self.key_bits, self.rounds,
            primary_q=primary_q if self.packed else None,
            **self._search_args(node),
        )
        by_pos = {perm3[j]: (c0, c1, c2)[j] for j in range(3)}
        if self.overlay:
            # base rows are counted through the alive prefix sums (masking
            # tombstones), the delta index is range-scanned with the same
            # bounds, and output rows are base matches then delta matches
            A = self.alive[node.node_id]
            nb = A[hi] - A[lo]
            dc0, dc1, dc2 = self.dscan_cols[node.node_id]
            dlo, dhi = _range_search(
                self.dscan_keys[node.node_id], dc0, dc1, dc2,
                lo_q, hi_q, self.key_bits, self.delta_rounds,
            )
            # clamp to the live delta rows: the wildcard upper bound packs
            # level with the pad rows' sentinel id, so pads fall in range
            dlo = jnp.minimum(dlo, self.dn)
            nd = jnp.minimum(dhi, self.dn) - dlo
            count = jnp.where(self.qvalid, nb + nd, 0)
        else:
            count = jnp.where(self.qvalid, hi - lo, 0)
        if not node.out_vars:  # all-constant pattern: pure existence filter
            return {}, jnp.minimum(count, 1)
        self.needed[f"scan{node.node_id}"] = count
        # rows come out in index order: sorted by the variable positions in
        # the order's (primary, secondary, tertiary) sequence — except under
        # an overlay, where delta matches append after the base run (the
        # tail determinism sort restores output order)
        var_by_pos = dict(node.var_slots)
        self._sorted[node.node_id] = () if self.overlay else tuple(
            var_by_pos[pos] for pos in perm3 if pos in var_by_pos
        )
        if self.overlay:
            j = jnp.arange(cap, dtype=jnp.int32)
            in_base = j < nb
            # rank-select the (A[lo]+j)-th live base row: the smallest
            # sorted position r with alive-prefix A[r+1] past that rank
            rb = jnp.clip(
                jnp.searchsorted(
                    A, A[lo] + j + 1, side="left"
                ).astype(jnp.int32) - 1,
                0, self.store_n - 1,
            )
            rd = jnp.clip(dlo + (j - nb), 0, self.delta_cap - 1)
            dby_pos = {perm3[k]: (dc0, dc1, dc2)[k] for k in range(3)}

            def gather(pos):
                return jnp.where(
                    in_base, by_pos[pos][rb], dby_pos[pos][rd]
                )

            valid = j < count
            cols = {v: gather(pos) for pos, v in node.var_slots}
            if node.eq_pairs:
                pat_vals = {pos: gather(pos) for pos in range(3)}
                for pa, pb in node.eq_pairs:
                    valid = valid & (pat_vals[pa] == pat_vals[pb])
                return _compact(cols, valid, cap)[:2]
            cols = {v: jnp.where(valid, c, UNBOUND) for v, c in cols.items()}
            return cols, jnp.minimum(count, cap)
        r = jnp.clip(lo + jnp.arange(cap, dtype=jnp.int32), 0, self.store_n - 1)
        valid = jnp.arange(cap) < count
        cols = {v: by_pos[pos][r] for pos, v in node.var_slots}
        if node.eq_pairs:
            pat_vals = {pos: by_pos[pos][r] for pos in range(3)}
            for pa, pb in node.eq_pairs:
                valid = valid & (pat_vals[pa] == pat_vals[pb])
            return _compact(cols, valid, cap)[:2]
        cols = {v: jnp.where(valid, c, UNBOUND) for v, c in cols.items()}
        return cols, jnp.minimum(count, cap)

    # -- joins ---------------------------------------------------------------

    def _bind_join(self, node: P.BindJoin):
        """Index nested-loop join: each left row's bound variables extend
        the bound prefix of the pattern's range scan — the pattern is
        never materialized independently."""
        lcols, ln = self._eval(node.left)
        cl = len(next(iter(lcols.values())))
        c0, c1, c2 = self.scan_cols[node.node_id]
        q = self.consts[self.scan_index[node.node_id]]
        perm3 = ORDERS[node.order]
        bound_by_pos = {pos: lcols[v] for pos, v in node.bound_slots}
        lvalid = jnp.arange(cl) < ln
        lo_q, hi_q = [], []
        for j in range(3):
            pos = perm3[j]
            if pos in node.const_slots:
                lo_q.append(jnp.broadcast_to(q[pos], (cl,)))
                hi_q.append(jnp.broadcast_to(q[pos], (cl,)))
            elif pos in bound_by_pos:
                # left-bound variable: an exact key for this row's lookup
                lo_q.append(bound_by_pos[pos])
                hi_q.append(bound_by_pos[pos])
            else:
                lo_q.append(jnp.full(cl, -1, jnp.int32))
                hi_q.append(jnp.full(cl, I32_MAX, jnp.int32))
        ppos = perm3[0]
        if ppos in node.const_slots:
            primary_q = jnp.broadcast_to(q[ppos], (cl,))
        else:  # bind-join orders put a bound slot first by construction
            primary_q = bound_by_pos[ppos]
        lo, hi = _range_search(
            self.scan_keys[node.node_id], c0, c1, c2,
            lo_q, hi_q, self.key_bits, self.rounds,
            primary_q=primary_q if self.packed else None,
            **self._search_args(node),
        )
        if self.overlay:
            # merged per-row match count: live base rows (alive-prefix
            # masked) plus delta rows in the same bounds — the second
            # range-scan arm, per left row
            A = self.alive[node.node_id]
            nb = A[hi] - A[lo]
            dc0, dc1, dc2 = self.dscan_cols[node.node_id]
            dlo, dhi = _range_search(
                self.dscan_keys[node.node_id], dc0, dc1, dc2,
                lo_q, hi_q, self.key_bits, self.delta_rounds,
            )
            dlo = jnp.minimum(dlo, self.dn)
            nd = jnp.minimum(dhi, self.dn) - dlo
            cnt = jnp.where(lvalid, nb + nd, 0)
        else:
            A = nb = dlo = None
            cnt = jnp.where(lvalid, hi - lo, 0)

        left_sorted = self._sorted.get(node.left.node_id, ())
        # expansion preserves left row order and emits each row's matches
        # in index order, so sortedness extends iff the left rows were
        # totally ordered (sorted by every left column) — and the index
        # order claim fails under an overlay (delta matches append after
        # the base run per left row)
        if set(left_sorted) >= set(node.left.out_vars):
            free_by_pos = dict(node.free_slots)
            self._sorted[node.node_id] = () if self.overlay else (
                left_sorted + tuple(
                    free_by_pos[pos] for pos in perm3 if pos in free_by_pos
                )
            )
        if node.kind == "left" and node.free_slots:
            # backfill rows append after the matches: order lost
            self._sorted[node.node_id] = ()

        if not node.free_slots:  # pure (anti-)semijoin: no new bindings
            self._sorted[node.node_id] = left_sorted
            if node.kind == "left":
                return lcols, ln
            return _compact(lcols, lvalid & (cnt > 0), cl)[:2]

        by_pos = {perm3[j]: (c0, c1, c2)[j] for j in range(3)}
        dby_pos = (
            {
                perm3[j]: self.dscan_cols[node.node_id][j]
                for j in range(3)
            }
            if self.overlay
            else None
        )
        cap = self.caps[f"bindC{node.node_id}"]
        if node.eq_pairs:
            return self._bind_join_grid(
                node, lcols, lvalid, lo, cnt, by_pos, cap,
                A=A, nb=nb, dlo=dlo, dby_pos=dby_pos,
            )
        # packed expansion: out row j belongs to the left row whose count
        # prefix-sum passes j (a log-width searchsorted), so matches land
        # directly packed — no (rows x fan-out) grid, no fan-out capacity,
        # no compaction pass
        cl = lvalid.shape[0]
        cum = jnp.cumsum(cnt)
        total = cum[cl - 1]
        j = jnp.arange(cap, dtype=jnp.int32)
        rowidx = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
        rowc = jnp.clip(rowidx, 0, cl - 1)
        prev = jnp.where(rowc > 0, cum[rowc - 1], 0)
        k = j - prev  # match index within this left row's merged run
        if self.overlay:
            nb_r = nb[rowc]
            in_base = k < nb_r
            rb = jnp.clip(
                jnp.searchsorted(
                    A, A[lo[rowc]] + k + 1, side="left"
                ).astype(jnp.int32) - 1,
                0, self.store_n - 1,
            )
            rd = jnp.clip(dlo[rowc] + (k - nb_r), 0, self.delta_cap - 1)
        else:
            r = jnp.clip(lo[rowc] + k, 0, self.store_n - 1)
        valid_out = j < jnp.minimum(total, cap)
        out_vals = {}
        for v in node.out_vars:
            if v in lcols:
                vals = lcols[v][rowc]
            else:
                pos = next(p for p, fv in node.free_slots if fv == v)
                if self.overlay:
                    vals = jnp.where(
                        in_base, by_pos[pos][rb], dby_pos[pos][rd]
                    )
                else:
                    vals = by_pos[pos][r]
            out_vals[v] = jnp.where(valid_out, vals, UNBOUND)
        if node.kind == "left":
            # backfill: left rows with no match append after the matches,
            # their free variables staying at the unbound sentinel
            un = lvalid & (cnt == 0)
            upos_raw = total + jnp.cumsum(un.astype(jnp.int32)) - 1
            upos = jnp.where(un & (upos_raw < cap), upos_raw, cap)
            for v in node.out_vars:
                if v in lcols:
                    out_vals[v] = (
                        out_vals[v].at[upos].set(lcols[v], mode="drop")
                    )
            total = total + jnp.sum(un.astype(jnp.int32))
        self.needed[f"bindC{node.node_id}"] = total
        return out_vals, jnp.minimum(total, cap)

    def _bind_join_grid(
        self, node, lcols, lvalid, lo, cnt, by_pos, cap,
        A=None, nb=None, dlo=None, dby_pos=None,
    ):
        """Grid expansion fallback for patterns with repeated free
        variables: pair validity depends on the gathered values, so the
        (rows x fan-out) grid plus a compaction pass is unavoidable.
        Under an overlay the grid covers each row's merged run — the
        first ``nb`` slots rank-select live base rows, the rest gather
        from the delta index."""
        cl = lvalid.shape[0]
        m = self.caps[f"bindM{node.node_id}"]
        self.needed[f"bindM{node.node_id}"] = jnp.max(cnt, initial=0)
        offs = jnp.arange(m, dtype=jnp.int32)
        if self.overlay:
            in_base = offs[None, :] < nb[:, None]
            rb = jnp.clip(
                jnp.searchsorted(
                    A, A[lo][:, None] + offs[None, :] + 1, side="left"
                ).astype(jnp.int32) - 1,
                0, self.store_n - 1,
            )
            rd = jnp.clip(
                dlo[:, None] + (offs[None, :] - nb[:, None]),
                0, self.delta_cap - 1,
            )

            def grid(pos):
                return jnp.where(
                    in_base, by_pos[pos][rb], dby_pos[pos][rd]
                )

        else:
            ridx = jnp.clip(lo[:, None] + offs[None, :], 0, self.store_n - 1)

            def grid(pos):
                return by_pos[pos][ridx]

        within = offs[None, :] < cnt[:, None]
        pairmask = within & lvalid[:, None]
        for pa, pb in node.eq_pairs:
            pairmask = pairmask & (grid(pa) == grid(pb))
        out_vals = {}
        for v in node.out_vars:
            if v in lcols:
                mat = jnp.broadcast_to(lcols[v][:, None], (cl, m))
            else:
                pos = next(p for p, fv in node.free_slots if fv == v)
                mat = grid(pos)
            out_vals[v] = mat.reshape(-1)
        flat_mask = pairmask.reshape(-1)
        if node.kind == "left":
            matched = jnp.sum(pairmask.astype(jnp.int32), axis=1)
            unmatched = lvalid & (matched == 0)
            for v in node.out_vars:
                tail = (
                    lcols[v]
                    if v in lcols
                    else jnp.full(cl, UNBOUND, jnp.int32)
                )
                out_vals[v] = jnp.concatenate([out_vals[v], tail])
            flat_mask = jnp.concatenate([flat_mask, unmatched])
        cols, n, total = _compact(out_vals, flat_mask, cap)
        self.needed[f"bindC{node.node_id}"] = total
        return cols, n

    def _join(self, node: P.Join):
        lcols, ln = self._eval(node.left)
        rcols, rn = self._eval(node.right)
        # zero-variable sides are existence filters: scale the other side
        if not node.left.out_vars and node.kind == "inner":
            return rcols, jnp.where(ln > 0, rn, 0)
        if not node.right.out_vars:
            if node.kind == "inner":
                return lcols, jnp.where(rn > 0, ln, 0)
            return lcols, ln  # OPTIONAL {} with no vars binds nothing
        if node.build_right:
            build_cols, bn, probe_cols, pn = rcols, rn, lcols, ln
        else:
            build_cols, bn, probe_cols, pn = lcols, ln, rcols, rn
        cb = len(next(iter(build_cols.values())))
        cp = len(next(iter(probe_cols.values()))) if probe_cols else 1
        cap = self.caps[f"joinC{node.node_id}"]
        pvalid = jnp.arange(cp) < pn

        if node.shared:
            m = self.caps[f"joinM{node.node_id}"]
            key = node.shared[0]
            bk = jnp.where(
                jnp.arange(cb) < bn, build_cols[key], I32_MAX
            )
            order = jnp.argsort(bk, stable=True)
            skeys = bk[order]
            pk = jnp.where(pvalid, probe_cols[key], -3)
            start = jnp.searchsorted(skeys, pk, side="left").astype(jnp.int32)
            end = jnp.searchsorted(skeys, pk, side="right").astype(jnp.int32)
            cnt = end - start
            self.needed[f"joinM{node.node_id}"] = jnp.max(
                jnp.where(pvalid, cnt, 0), initial=0
            )
        else:  # cross join: every valid probe row spans the whole build side
            m = cb
            order = jnp.arange(cb, dtype=jnp.int32)
            start = jnp.zeros(cp, jnp.int32)
            cnt = jnp.where(pvalid, bn, 0).astype(jnp.int32)

        offs = jnp.arange(m, dtype=jnp.int32)
        bidx = start[:, None] + offs[None, :]
        within = offs[None, :] < cnt[:, None]
        brow = order[jnp.clip(bidx, 0, cb - 1)]
        pairmask = within & pvalid[:, None]
        for v in node.shared[1:]:
            pairmask = pairmask & (
                build_cols[v][brow] == probe_cols[v][:, None]
            )

        out_vals: dict[str, jnp.ndarray] = {}
        for v in node.out_vars:
            if probe_cols and v in probe_cols:
                mat = jnp.broadcast_to(probe_cols[v][:, None], (cp, m))
            else:
                mat = build_cols[v][brow]
            out_vals[v] = mat.reshape(-1)
        flat_mask = pairmask.reshape(-1)

        if node.kind == "left":
            # unmatched-row backfill: preserved left rows with the optional
            # side's variables left at the unbound sentinel
            matched = jnp.sum(pairmask.astype(jnp.int32), axis=1)
            unmatched = pvalid & (matched == 0)
            cat_vals = {}
            for v in node.out_vars:
                if probe_cols and v in probe_cols:
                    tail = probe_cols[v]
                else:
                    tail = jnp.full(cp, UNBOUND, jnp.int32)
                cat_vals[v] = jnp.concatenate([out_vals[v], tail])
            flat_mask = jnp.concatenate([flat_mask, unmatched])
            out_vals = cat_vals

        cols, n, total = _compact(out_vals, flat_mask, cap)
        self.needed[f"joinC{node.node_id}"] = total
        return cols, n

    # -- union / optional-chain provenance ------------------------------------

    def _union(self, node: P.UnionNode):
        """Fused concat-with-provenance: every arm's packed rows scatter
        into one output table at that arm's running offset (arm-major
        order — a row's provenance is its arm's offset range); variables
        an arm does not bind stay at the unbound sentinel."""
        arm_results = [self._eval(a) for a in node.arms]
        cap = self.caps[f"unionC{node.node_id}"]
        out = {v: jnp.full(cap, UNBOUND, jnp.int32) for v in node.out_vars}
        offset = jnp.int32(0)
        for acols, an in arm_results:
            acap = len(next(iter(acols.values()))) if acols else 1
            j = jnp.arange(acap, dtype=jnp.int32)
            pos = offset + j
            keep = (j < an) & (pos < cap)
            idx = jnp.where(keep, pos, cap)
            for v in node.out_vars:
                if v in acols:
                    out[v] = out[v].at[idx].set(acols[v], mode="drop")
            offset = offset + an.astype(jnp.int32)
        self.needed[f"unionC{node.node_id}"] = offset
        self._sorted[node.node_id] = ()
        return out, jnp.minimum(offset, cap)

    def _tag_rows(self, node: P.TagRows):
        """Append the packed row index as a synthetic column — the
        provenance an OPTIONAL bind-join chain joins back on.  Row ids are
        strictly increasing, so any known sort sequence extends by them."""
        cols, n = self._eval(node.child)
        cap = len(next(iter(cols.values()))) if cols else 1
        out = dict(cols)
        out[node.var] = jnp.arange(cap, dtype=jnp.int32)
        self._sorted[node.node_id] = (
            self._sorted.get(node.child.node_id, ()) + (node.var,)
        )
        return out, n

    def _left_finish(self, node: P.LeftFinish):
        """Finish a multi-pattern OPTIONAL chain: the chain's packed rows
        are the matches; left rows whose row id never reached the chain
        output append after them with the group's variables unbound."""
        lcols, ln = self._eval(node.left)
        rcols, rn = self._eval(node.right)
        capL = len(next(iter(lcols.values())))
        capR = len(next(iter(rcols.values())))
        cap = self.caps[f"leftC{node.node_id}"]
        lvalid = jnp.arange(capL) < ln
        rvalid = jnp.arange(capR) < rn
        rid = rcols[node.rowid]
        matched = (
            jnp.zeros(capL, bool)
            .at[jnp.where(rvalid, rid, capL)]
            .set(True, mode="drop")
        )
        unmatched = lvalid & ~matched
        out = {v: jnp.full(cap, UNBOUND, jnp.int32) for v in node.out_vars}
        jr = jnp.arange(capR, dtype=jnp.int32)
        idx_r = jnp.where(rvalid & (jr < cap), jr, cap)
        for v in node.out_vars:
            if v in rcols:
                out[v] = out[v].at[idx_r].set(rcols[v], mode="drop")
        upos_raw = rn + jnp.cumsum(unmatched.astype(jnp.int32)) - 1
        upos = jnp.where(unmatched & (upos_raw < cap), upos_raw, cap)
        for v in node.out_vars:
            if v in lcols:
                out[v] = out[v].at[upos].set(lcols[v], mode="drop")
        total = rn + jnp.sum(unmatched.astype(jnp.int32))
        self.needed[f"leftC{node.node_id}"] = total
        self._sorted[node.node_id] = ()
        return out, jnp.minimum(total, cap)

    # -- filters -------------------------------------------------------------

    def _gather_side(self, array, ids):
        return array[jnp.clip(ids, 0, array.shape[0] - 1)]

    def _cmp(self, c: P.LCmp, cols: dict, cap: int):
        is_lit, is_num, str_rank, num_rank = self.vt_arrays[:4]

        def var_ids(o: P.LOperand):
            if o.var in cols:
                return cols[o.var]
            return jnp.full(cap, UNBOUND, jnp.int32)  # never-bound variable

        def rank_of(o: P.LOperand, table, okmask):
            ids = var_ids(o)
            ok = (ids >= 0) & self._gather_side(okmask, ids)
            return self._gather_side(table, ids), ok

        op = c.op
        if c.mode in ("num", "str"):
            table, okmask = (
                (num_rank, is_num) if c.mode == "num" else (str_rank, is_lit)
            )
            rank, ok = rank_of(c.lhs, table, okmask)
            lo = self.fops[c.rhs.slot]
            hi = self.fops[c.rhs.slot + 1]
            present = lo < hi
            if op == "<":
                return ok & (rank < lo)
            if op == "<=":
                return ok & (rank < hi)
            if op == ">":
                return ok & (rank >= hi)
            if op == ">=":
                return ok & (rank >= lo)
            if op == "=":
                return ok & present & (rank == lo)
            return ok & ~(present & (rank == lo))  # !=
        if c.mode == "term":
            x = var_ids(c.lhs)
            if c.rhs.kind == "var":
                y = var_ids(c.rhs)
                both = (x >= 0) & (y >= 0)
                return both & ((x == y) if op == "=" else (x != y))
            cid = self.fops[c.rhs.slot]
            bound = x >= 0
            return bound & ((x == cid) if op == "=" else (x != cid))
        # mode 'vv': ordering between two variables — numeric when both
        # numeric, else literal-body order when both literals, else false
        x, y = var_ids(c.lhs), var_ids(c.rhs)
        bound = (x >= 0) & (y >= 0)
        xn = self._gather_side(num_rank, x)
        yn = self._gather_side(num_rank, y)
        xs = self._gather_side(str_rank, x)
        ys = self._gather_side(str_rank, y)
        both_num = self._gather_side(is_num, x) & self._gather_side(is_num, y)
        both_lit = self._gather_side(is_lit, x) & self._gather_side(is_lit, y)

        def rel(a, b):
            if op == "<":
                return a < b
            if op == "<=":
                return a <= b
            if op == ">":
                return a > b
            return a >= b

        return bound & jnp.where(
            both_num, rel(xn, yn), both_lit & rel(xs, ys)
        )

    def _expr(self, e: P.LExpr, cols: dict, cap: int):
        if isinstance(e, P.LCmp):
            return self._cmp(e, cols, cap)
        if isinstance(e, P.LBound):
            if e.var in cols:
                return cols[e.var] >= 0
            return jnp.zeros(cap, bool)
        if isinstance(e, P.LNot):
            return ~self._expr(e.expr, cols, cap)
        if isinstance(e, P.LAnd):
            return self._expr(e.lhs, cols, cap) & self._expr(e.rhs, cols, cap)
        return self._expr(e.lhs, cols, cap) | self._expr(e.rhs, cols, cap)

    def _filter(self, node: P.Filter):
        cols, n = self._eval(node.child)
        self._sorted[node.node_id] = self._sorted.get(node.child.node_id, ())
        if not cols:  # zero-variable table: expr sees only unbound vars
            cap = 1
            keep = self._expr(node.expr, cols, cap)
            return cols, jnp.where(keep[0], n, 0)
        cap = len(next(iter(cols.values())))
        mask = self._expr(node.expr, cols, cap) & (jnp.arange(cap) < n)
        return _compact(cols, mask, cap)[:2]

    # -- tail ----------------------------------------------------------------

    def _already_ordered(self, node) -> bool:
        """True when the child's known sort sequence already starts with
        this node's output columns — the determinism sort is a no-op."""
        child_sorted = self._sorted.get(node.child.node_id, ())
        return child_sorted[: len(node.out_vars)] == node.out_vars

    def _project(self, node: P.Project):
        cols, n = self._eval(node.child)
        child_sorted = self._sorted.get(node.child.node_id, ())
        kept = []
        for v in child_sorted:  # dropping a sort column cuts the sequence
            if v not in node.out_vars:
                break
            kept.append(v)
        self._sorted[node.node_id] = tuple(kept)
        cap = len(next(iter(cols.values()))) if cols else 1
        out = {}
        for v in node.out_vars:
            out[v] = cols[v] if v in cols else jnp.full(cap, UNBOUND, jnp.int32)
        return out, n

    def _group(self, node: P.Group):
        """GROUP BY + COUNT via a device segment-sum: sort by the key
        columns, find segment boundaries, count each segment's
        contributions (1 per row for COUNT(*), boundness of the argument
        for COUNT(?v)), and emit one packed row per segment — output rows
        are unique in the key tuple, so they come out sorted by it."""
        cols, n = self._eval(node.child)
        cap = len(next(iter(cols.values()))) if cols else 1
        valid = jnp.arange(cap) < n
        if node.count_var is None:
            contrib = valid.astype(jnp.int32)
        else:
            cv = cols.get(node.count_var)
            contrib = (
                jnp.zeros(cap, jnp.int32)
                if cv is None
                else (valid & (cv >= 0)).astype(jnp.int32)
            )
        if not node.keys:
            # the global group: exactly one row, even over zero solutions
            total = jnp.sum(contrib)
            out = {
                v: jnp.zeros(1, jnp.int32).at[0].set(total)
                for v in node.out_vars  # validation: only the alias
            }
            self._sorted[node.node_id] = node.out_vars
            return out, jnp.int32(1)
        key_cols = {
            k: cols.get(k, jnp.full(cap, UNBOUND, jnp.int32))
            for k in node.keys
        }
        perm, _ = _sort_perm(key_cols, node.keys, n, cap)
        skeys = {k: c[perm] for k, c in key_cols.items()}
        svalid = valid[perm]
        scontrib = contrib[perm]
        same_prev = jnp.ones(cap, bool)
        for k in node.keys:
            c = skeys[k]
            same_prev = same_prev & jnp.concatenate(
                [jnp.zeros(1, bool), c[1:] == c[:-1]]
            )
        boundary = svalid & ~same_prev
        gid_raw = jnp.cumsum(boundary.astype(jnp.int32)) - 1
        gid = jnp.where(svalid, gid_raw, cap)
        counts = jnp.zeros(cap, jnp.int32).at[gid].add(scontrib, mode="drop")
        n_groups = jnp.sum(boundary.astype(jnp.int32))
        bidx = jnp.where(boundary, gid_raw, cap)
        out = {}
        for v in node.out_vars:
            if v == node.alias:
                out[v] = counts
            else:  # a selected group key: its value at each segment head
                out[v] = (
                    jnp.full(cap, UNBOUND, jnp.int32)
                    .at[bidx]
                    .set(skeys[v], mode="drop")
                )
        # output rows are unique in the full key tuple and sorted by it,
        # so any column extension of the key sequence stays sorted
        seq: list[str] = []
        for k in node.keys:
            if k not in node.out_vars:
                break
            seq.append(k)
        if len(seq) == len(node.keys):
            seq += [v for v in node.out_vars if v not in seq]
        self._sorted[node.node_id] = tuple(seq)
        return out, n_groups

    def _distinct(self, node: P.Distinct):
        cols, n = self._eval(node.child)
        self._sorted[node.node_id] = node.out_vars
        if not cols:
            return cols, jnp.minimum(n, 1)
        cap = len(next(iter(cols.values())))
        if self._already_ordered(node):
            sorted_cols, svalid = cols, jnp.arange(cap) < n
        else:
            perm, valid = _sort_perm(cols, node.out_vars, n, cap)
            sorted_cols = {v: c[perm] for v, c in cols.items()}
            svalid = valid[perm]
        same_prev = jnp.ones(cap, bool)
        for v in node.out_vars:
            c = sorted_cols[v]
            same_prev = same_prev & jnp.concatenate(
                [jnp.zeros(1, bool), c[1:] == c[:-1]]
            )
        keep = svalid & ~same_prev
        return _compact(sorted_cols, keep, cap)[:2]

    def _sort(self, node: P.Sort):
        cols, n = self._eval(node.child)
        self._sorted[node.node_id] = node.out_vars
        if not cols:
            return cols, n
        if self._already_ordered(node):
            return cols, n
        cap = len(next(iter(cols.values())))
        perm, valid = _sort_perm(cols, node.out_vars, n, cap)
        return {v: c[perm] for v, c in cols.items()}, n

    def _order_by(self, node: P.OrderBy):
        """Value-typed ORDER BY: term columns key on ``order_rank`` (the
        store-wide value order permutation), count columns on their raw
        integer value; descending keys negate; every output column
        tie-breaks in term-id order so the result stays deterministic.
        Elided when the child's tracked sortedness already realizes the
        requested order (possible only when value order == term-id
        order, or when every key is a count column)."""
        cols, n = self._eval(node.child)
        self._sorted[node.node_id] = ()
        if not cols:
            return cols, n
        cap = len(next(iter(cols.values())))
        keyvars = tuple(v for v, _, _ in node.keys)
        desired = keyvars + tuple(
            v for v in node.out_vars if v not in keyvars
        )
        elidable = all(asc for _, asc, _ in node.keys) and (
            self.order_is_tid
            or all(is_count for _, _, is_count in node.keys)
        )
        child_sorted = self._sorted.get(node.child.node_id, ())
        if elidable and child_sorted[: len(desired)] == desired:
            self._sorted[node.node_id] = child_sorted
            return cols, n
        valid = jnp.arange(cap) < n
        order_rank = self.vt_arrays[4]
        keys = []
        for v, asc, is_count in node.keys:
            c = cols.get(v, jnp.full(cap, UNBOUND, jnp.int32))
            if is_count:
                k = c
            else:
                # unbound (-1) keys below every rank: unbound-first
                # ascending, unbound-last descending
                k = jnp.where(
                    c >= 0, self._gather_side(order_rank, c), jnp.int32(-1)
                )
            if not asc:
                k = -k
            keys.append(jnp.where(valid, k, I32_MAX))
        for v in node.out_vars:  # term-id tie-break: determinism
            c = cols.get(v, jnp.full(cap, UNBOUND, jnp.int32))
            keys.append(jnp.where(valid, c, I32_MAX))
        payload = jnp.arange(cap, dtype=jnp.int32)
        out = jax.lax.sort(
            tuple(keys) + (payload,), num_keys=len(keys), is_stable=True
        )
        perm = out[-1]
        return {v: c[perm] for v, c in cols.items()}, n

    # -- dispatch ------------------------------------------------------------

    def _eval(self, node: P.Node):
        hit = self._memo.get(node.node_id)
        if hit is not None:
            return hit
        res = self._eval_inner(node)
        self._memo[node.node_id] = res
        return res

    def _eval_inner(self, node: P.Node):
        if isinstance(node, P.Scan):
            return self._scan(node)
        if isinstance(node, P.BindJoin):
            return self._bind_join(node)
        if isinstance(node, P.Join):
            return self._join(node)
        if isinstance(node, P.UnionNode):
            return self._union(node)
        if isinstance(node, P.TagRows):
            return self._tag_rows(node)
        if isinstance(node, P.LeftFinish):
            return self._left_finish(node)
        if isinstance(node, P.Filter):
            return self._filter(node)
        if isinstance(node, P.Project):
            return self._project(node)
        if isinstance(node, P.Group):
            return self._group(node)
        if isinstance(node, P.Distinct):
            return self._distinct(node)
        if isinstance(node, P.Sort):
            return self._sort(node)
        if isinstance(node, P.OrderBy):
            return self._order_by(node)
        if isinstance(node, P.Limit):
            cols, n = self._eval(node.child)
            self._sorted[node.node_id] = self._sorted.get(
                node.child.node_id, ()
            )
            # the limit value is per-query runtime data (plan sharing);
            # -1 marks a padded batch row, where the count is 0 anyway
            return cols, jnp.where(
                self.qlimit >= 0, jnp.minimum(n, self.qlimit), n
            )
        raise TypeError(f"unknown plan node {node!r}")

    def run(
        self, scan_cols_flat, scan_keys_flat, scan_prim_flat,
        dscan_cols_flat, dscan_keys_flat, alive_flat, dn,
        vt_arrays, consts, fops, qvalid, qlimit,
    ):
        self.scan_cols = {
            s.node_id: scan_cols_flat[3 * i : 3 * i + 3]
            for i, s in enumerate(self.plan.scans)
        }
        self.scan_keys = {
            s.node_id: scan_keys_flat[i] if self.packed else None
            for i, s in enumerate(self.plan.scans)
        }
        self.scan_prim = {
            s.node_id: scan_prim_flat[i] if self.packed else None
            for i, s in enumerate(self.plan.scans)
        }
        self.dscan_cols = {
            s.node_id: dscan_cols_flat[3 * i : 3 * i + 3]
            for i, s in enumerate(self.plan.scans)
        }
        self.dscan_keys = {
            s.node_id: dscan_keys_flat[i] if self.packed else None
            for i, s in enumerate(self.plan.scans)
        }
        self.alive = {
            s.node_id: alive_flat[i] for i, s in enumerate(self.plan.scans)
        }
        self.dn = dn
        self.vt_arrays = vt_arrays
        self.consts = consts
        self.fops = fops
        self.qvalid = qvalid
        self.qlimit = qlimit
        self.needed = {}
        self._memo = {}
        cols, n = self._eval(self.plan.root)
        out_cols = tuple(cols.get(v) for v in self.plan.root.out_vars)
        return out_cols, n, dict(self.needed)


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------


def _initial_caps(plan: P.Plan, floors: dict[str, int]) -> dict[str, int]:
    caps: dict[str, int] = {}
    seen: set[int] = set()

    def walk(node: P.Node) -> None:
        if node.node_id in seen:  # the plan is a DAG: visit shared subtrees once
            return
        seen.add(node.node_id)
        if isinstance(node, P.Scan):
            if node.out_vars:
                caps[f"scan{node.node_id}"] = next_pow2(max(node.est, 1))
            return
        if isinstance(node, P.BindJoin):
            walk(node.left)
            if node.free_slots:
                if node.eq_pairs:  # grid fallback needs the fan-out cap
                    caps[f"bindM{node.node_id}"] = 8
                caps[f"bindC{node.node_id}"] = next_pow2(
                    min(max(node.est, 16), 1 << 22)
                )
            return
        if isinstance(node, P.Join):
            walk(node.left)
            walk(node.right)
            if node.right.out_vars and (
                node.left.out_vars or node.kind == "left"
            ):
                if node.shared:
                    caps[f"joinM{node.node_id}"] = 8
                # clamp the initial guess: a mis-estimated cross join must
                # not allocate a giant table up front (feedback grows it to
                # the exact need if the result really is that large)
                caps[f"joinC{node.node_id}"] = next_pow2(
                    min(max(node.est, 16), 1 << 22)
                )
            return
        if isinstance(node, P.UnionNode):
            for arm in node.arms:
                walk(arm)
            caps[f"unionC{node.node_id}"] = next_pow2(
                min(max(node.est, 16), 1 << 22)
            )
            return
        if isinstance(node, P.LeftFinish):
            walk(node.left)
            walk(node.right)
            caps[f"leftC{node.node_id}"] = next_pow2(
                min(max(node.est, 16), 1 << 22)
            )
            return
        for c in P._children(node):
            walk(c)

    walk(plan.root)
    for k, v in floors.items():
        if k in caps:
            caps[k] = max(caps[k], v)
    return caps


class Executor:
    """Per-store query executor: plan cache, capacity memory, compiled
    pipeline cache.  Get one via :func:`get_executor`."""

    #: route eligible small batches through the fused scan-join chain
    #: (``repro.serve.fastpath``); tests flip this off to force the
    #: general pipeline for equivalence checks
    fastpath_enabled = True

    def __init__(self, store: TripleStore):
        self.store = store
        self._plans: dict[tuple, P.Plan] = {}
        self._floors: dict[tuple, dict[str, int]] = {}
        self._compiled: dict[tuple, callable] = {}
        self._fastpaths: dict[tuple, "FP.SigFastPath | None"] = {}
        self.dispatches = 0  # total jitted pipeline dispatches (for tests)

    # -- plans ---------------------------------------------------------------

    def plan(self, q: A.SelectQuery) -> P.Plan:
        sig = q.signature()
        plan = self._plans.get(sig)
        if plan is None:
            plan = P.plan_query(self.store, q)
            self._plans[sig] = plan
        return plan

    # -- compilation ---------------------------------------------------------

    def _get_compiled(
        self, plan: P.Plan, caps: dict[str, int], bpad: int,
        ov: tuple[int, bool] | None = None,
    ):
        """``ov`` switches on the overlay arm: ``(delta row capacity,
        delta index packable)`` — part of the cache key, so pure-read
        pipelines never carry overlay code."""
        key = (plan.sig, tuple(sorted(caps.items())), bpad, ov)
        fn = self._compiled.get(key)
        if fn is not None:
            # signature-memo hit: this (plan, capacities, batch-pad) shape
            # re-dispatches without tracing a new pipeline
            get_registry().inc("exec.pipeline_cache_hit")
        else:
            get_registry().inc("exec.pipeline_cache_miss")
            if self.store.n_triples == 0:
                # overlay over an empty base: dummy single-row base
                # operands, every base range comes out empty
                base_packed = True
                prim_rounds = {s.node_id: 1 for s in plan.scans}
            else:
                base_packed = self.store.device_keys("spo") is not None
                prim_rounds = (
                    {
                        s.node_id: self.store.primary_rounds(s.order)
                        for s in plan.scans
                    }
                    if base_packed
                    else None
                )
            # a combined term table that overflows the packed key fields
            # forces both arms onto the 3-column lexicographic fallback
            packed = base_packed and (ov is None or ov[1])
            if not packed:
                prim_rounds = None
            order_is_tid = (
                value_table(self.store).order_is_tid
                if plan.needs_values and ov is None
                else False  # overlay term ids append out of rendered order
            )
            delta_cap = ov[0] if ov else 1
            lowerer = _Lowerer(
                plan,
                caps,
                max(self.store.n_triples, 1),
                self.store.KEY_BITS,
                packed,
                prim_rounds,
                order_is_tid,
                overlay=ov is not None,
                delta_cap=delta_cap,
                delta_rounds=max(1, int(delta_cap).bit_length()),
            )

            def single(
                scan_cols_flat, scan_keys_flat, scan_prim_flat,
                dscan_cols_flat, dscan_keys_flat, alive_flat, dn,
                vt_arrays, consts, fops, qvalid, qlimit,
            ):
                return lowerer.run(
                    scan_cols_flat, scan_keys_flat, scan_prim_flat,
                    dscan_cols_flat, dscan_keys_flat, alive_flat, dn,
                    vt_arrays, consts, fops, qvalid, qlimit,
                )

            fn = jax.jit(
                jax.vmap(
                    single,
                    in_axes=(None,) * 8 + (0, 0, 0, 0),
                )
            )
            self._compiled[key] = fn
        return fn

    # -- execution -----------------------------------------------------------

    def execute(
        self, plan: P.Plan, queries: list[A.SelectQuery], view=None
    ) -> BatchResult:
        """Run signature-equal ``queries`` as one micro-batch: encode each
        query's constants, then dispatch through :meth:`execute_encoded`.
        ``view`` (a :class:`repro.live.delta.OverlayView` over this
        executor's store) answers over ``base ⊕ delta``; an inactive view
        (empty overlay) takes the pure-read fast path untouched."""
        act = view is not None and view.active
        enc = view if act else self.store
        bsz = len(queries)
        consts = np.full((bsz, len(plan.scans), 3), -2, np.int32)
        fops = np.zeros((bsz, max(plan.n_filter_ops, 1)), np.int32)
        vt = value_table(enc) if plan.has_filters else None
        for i, q in enumerate(queries):
            consts[i] = P.encode_scan_consts(enc, plan, q)
            if plan.n_filter_ops:
                fops[i] = P.encode_filter_ops(enc, vt, q.filters)
        limits = np.asarray(
            [-1 if q.limit is None else q.limit for q in queries], np.int32
        )
        return self.execute_encoded(
            plan, consts, fops, limits, view=view if act else None
        )

    def execute_encoded(
        self,
        plan: P.Plan,
        consts: np.ndarray,
        fops: np.ndarray | None = None,
        limits: np.ndarray | None = None,
        view=None,
    ) -> BatchResult:
        """The pre-encoded hot path (the benchmark's unit of work): run a
        ``[B, n_scans, 3]`` int32 constants batch (``-1`` variable slot,
        ``-2`` unknown constant) plus optional ``[B, n_filter_ops]`` filter
        operands, padded to a power-of-two batch, re-dispatching only when
        a capacity was exceeded.  ``view`` (an *active* overlay view whose
        constants/filter operands were encoded against it) adds the second
        scan arm; its results decode against the view's combined terms."""
        store = self.store
        out_vars = plan.root.out_vars
        bsz = consts.shape[0]
        if store.n_triples == 0 and view is None:
            if plan.global_agg_alias is not None:
                # a global COUNT answers one zero row even over nothing
                lim = (
                    np.full(bsz, -1, np.int64)
                    if limits is None
                    else np.asarray(limits, np.int64)[:bsz]
                )
                counts = np.where(lim >= 0, np.minimum(lim, 1), 1)
                return BatchResult(
                    store=store,
                    vars=out_vars,
                    cols={v: np.zeros((bsz, 1), np.int32) for v in out_vars},
                    counts=counts.astype(np.int64),
                    agg_vars=plan.agg_vars,
                )
            return BatchResult(
                store=store,
                vars=out_vars,
                cols={v: np.full((bsz, 1), -1, np.int32) for v in out_vars},
                counts=np.zeros(bsz, np.int64),
                agg_vars=plan.agg_vars,
            )
        if (
            view is None
            and self.fastpath_enabled
            and bsz <= FP.MAX_BATCH
        ):
            fp = self._fastpaths.get(plan.sig, _FP_UNSET)
            if fp is _FP_UNSET:
                fp = FP.build(self, plan)
                self._fastpaths[plan.sig] = fp
            if fp is not None:
                res = fp.dispatch(consts, limits, bsz)
                if res is not None:  # None: outgrew the small-batch regime
                    fcols, counts = res
                    return BatchResult(
                        store=store,
                        vars=out_vars,
                        cols=dict(zip(out_vars, fcols)),
                        counts=counts,
                        agg_vars=plan.agg_vars,
                    )
        bpad = next_pow2(max(bsz, 1))
        if fops is None:
            fops = np.zeros((bsz, max(plan.n_filter_ops, 1)), np.int32)
        if limits is None:
            limits = np.full(bsz, -1, np.int32)
        if bpad > bsz:
            consts = np.concatenate(
                [consts, np.full((bpad - bsz, len(plan.scans), 3), -2, np.int32)]
            )
            fops = np.concatenate(
                [fops, np.zeros((bpad - bsz, fops.shape[1]), np.int32)]
            )
            limits = np.concatenate(
                [limits, np.full(bpad - bsz, -1, np.int32)]
            )
        qvalid = np.zeros(bpad, bool)
        qvalid[:bsz] = True
        enc = view if view is not None else store
        vt = value_table(enc) if plan.needs_values else None

        n_scans = len(plan.scans)

        def put(x):
            return jax.device_put(x, store.device)

        z = put(np.zeros(1, np.int32))
        if store.n_triples == 0:
            # empty base under an active overlay: single-row dummies keep
            # every gather in range; the alive prefix sums (length 1) make
            # every base range empty
            scan_cols_flat = (z,) * (3 * n_scans)
            scan_keys_flat = ((z, z),) * n_scans
            scan_prim_flat = (z,) * n_scans
        else:
            scan_cols_flat = tuple(
                c for s in plan.scans for c in store.device_cols(s.order)
            )
            if store.device_keys("spo") is not None:
                scan_keys_flat = tuple(
                    store.device_keys(s.order) for s in plan.scans
                )
                scan_prim_flat = tuple(
                    store.device_primary_starts(s.order) for s in plan.scans
                )
            else:
                scan_keys_flat = ((z, z),) * n_scans
                scan_prim_flat = (z,) * n_scans
        if view is not None:
            ov_packed = view.delta.device_keys("spo") is not None
            ov = (view.delta.n_triples, ov_packed)
            dscan_cols_flat = tuple(
                c for s in plan.scans for c in view.delta.device_cols(s.order)
            )
            if ov_packed:
                dscan_keys_flat = tuple(
                    view.delta.device_keys(s.order) for s in plan.scans
                )
            else:
                dscan_keys_flat = ((z, z),) * n_scans
            alive_flat = tuple(view.alive(s.order) for s in plan.scans)
            dn_j = put(np.int32(view.n_delta))
        else:
            ov = None
            dscan_cols_flat = (z,) * (3 * n_scans)
            dscan_keys_flat = ((z, z),) * n_scans
            alive_flat = (z,) * n_scans
            dn_j = put(np.int32(0))
        if plan.needs_values:
            vt_arrays = (
                vt.is_lit, vt.is_num, vt.str_rank, vt.num_rank, vt.order_rank
            )
        else:
            zb = put(np.zeros(1, bool))
            vt_arrays = (zb, zb, z, z, z)

        floors = self._floors.setdefault(plan.sig, {})
        caps = _initial_caps(plan, floors)
        consts_j, fops_j, qvalid_j, qlimit_j = put(
            (consts, fops, qvalid, limits)
        )
        reg = get_registry()
        tracer = get_tracer()
        label = plan_label(plan.sig)
        reg.inc("exec.batches")
        reg.inc("exec.queries", bsz)
        for round_i in range(_MAX_GROW_ROUNDS):
            t0 = time.perf_counter_ns()
            fn = self._get_compiled(plan, caps, bpad, ov)
            out_cols, n, needed = fn(
                scan_cols_flat, scan_keys_flat, scan_prim_flat,
                dscan_cols_flat, dscan_keys_flat, alive_flat, dn_j,
                vt_arrays, consts_j, fops_j, qvalid_j, qlimit_j,
            )
            self.dispatches += 1
            grown = False
            for k, arr in needed.items():
                want = int(np.max(np.asarray(arr)))
                if want > caps[k]:
                    caps[k] = next_pow2(want)
                    floors[k] = max(floors.get(k, 0), caps[k])
                    grown = True
                    # grow-only buffer growth: remembered per signature, so
                    # a steady workload stops paying this re-dispatch
                    reg.inc("exec.cap_growth")
            t1 = time.perf_counter_ns()
            reg.inc("exec.dispatches")
            reg.observe("exec.dispatch_ms", (t1 - t0) / 1e6)
            if round_i > 0:
                reg.inc("exec.redispatches")
            if tracer.enabled:
                tracer.add_complete(
                    "redispatch" if round_i > 0 else "dispatch",
                    "exec", t0, t1,
                    plan=label, batch=bsz, round=round_i,
                    grown=grown,
                )
            if not grown:
                break
        else:
            raise RuntimeError(
                "executor capacity feedback did not converge "
                f"(caps={caps}) — pathological query?"
            )
        counts = np.asarray(n)[:bsz].astype(np.int64)
        cols = {
            v: np.asarray(c)[:bsz]
            for v, c in zip(out_vars, out_cols)
        } if out_cols else {}
        return BatchResult(
            store=enc, vars=out_vars, cols=cols, counts=counts,
            agg_vars=plan.agg_vars,
        )

    def solve(self, q: A.SelectQuery) -> BatchResult:
        return self.execute(self.plan(q), [q])

    def warmup(self, top_k: int = 2) -> int:
        """Pre-trace the dominant interactive shapes — the 1-, 2- and
        3-pattern star chains anchored on the store's ``top_k`` most
        frequent predicates — at batch pad 1, so a freshly started
        server answers its first small-batch query without paying a jit
        compile.  Returns the number of signatures warmed (compilation
        happens as a side effect of actually executing each shape
        once; the capacity floors learned here persist too)."""
        store = self.store
        if store.n_triples == 0:
            return 0
        prim = np.asarray(store.indexes["pos"].cols[0])
        preds, cnts = np.unique(prim, return_counts=True)
        top = [
            store.decode_term(int(p))
            for p in preds[np.argsort(cnts)[::-1][: max(top_k, 1)]]
        ]
        texts = []
        for p in top:
            texts.append(f"SELECT * WHERE {{ ?s {p} ?o }}")
        if len(top) >= 2:
            p0, p1 = top[0], top[1]
            texts.append(
                f"SELECT * WHERE {{ ?s {p0} ?o0 . ?s {p1} ?o1 }}"
            )
            texts.append(
                "SELECT * WHERE { "
                + f"?s {p0} ?o0 . ?s {p1} ?o1 . ?s {p0} ?o2 "
                + "}"
            )
        warmed = 0
        for text in texts:
            try:
                q = A.parse_select(text)
            except ValueError:  # a predicate the query grammar can't spell
                continue
            # compile and device errors propagate: a server that cannot
            # run its dominant shapes must not start
            self.execute(self.plan(q), [q])
            warmed += 1
        return warmed


def get_executor(store: TripleStore) -> Executor:
    ex = getattr(store, "_serve_executor", None)
    if ex is None:
        ex = Executor(store)
        store._serve_executor = ex
    return ex


def solve_select(store: TripleStore, q: A.SelectQuery) -> BatchResult:
    """One-shot convenience: plan + execute a single query."""
    return get_executor(store).solve(q)
