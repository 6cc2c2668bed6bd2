"""Predicate Join Tuple Table — the paper's index-join structure, TPU-native.

The paper's PJTT maps ``value(join condition B) -> {subjects of the parent
triples map}`` so that an Object Join Map becomes an index join (one probe per
child row) instead of a nested-loop join.

Join keys and subjects are dictionary-encoded int32 term-value ids (see
``repro.data.encoder``), so the structure is built from flat int32 arrays:
the parent's ``(key, subject)`` pairs sorted once, and a probe is a pair of
``searchsorted`` calls yielding a ``[start, end)`` span — sequential access,
one sort to build.

The engine's OJM steps hash the *exact ragged* layout: the host finds each
child row's span in a host copy of the sorted keys (:func:`spans`) and lays
the batch's candidates out one lane each, row by row, in ``lane_bucket(T)``
lanes for ``T`` candidates (:func:`ragged_lanes`).  :func:`probe_sorted`
gives the *padded-ragged* layout, ``(m, max_matches)`` subject ids plus a
validity mask; it remains for ``core/distributed.py``'s sharded probe and
as the reference the ragged lanes are tested against.

A span holds each distinct ``(key, subject)`` pair once, as the paper's PJTT
maps a value to a *set* of subjects: repeated pairs are masked with a ``-1``
subject and moved out of their key's span to a tail under the key
``INT32_MAX``, which is never a dictionary id.  So a key's span count is its
number of distinct subjects, every lane of a span is a candidate, and the
spans alone size and lay out a join.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.hashset import next_pow2
from repro.data.encoder import NULL_ID

_SUBJ_MASKED = np.int32(NULL_ID)  # a repeated pair, or a NULL subject
_I32_MAX = jnp.iinfo(jnp.int32).max


class PJTTSorted(NamedTuple):
    skeys: jnp.ndarray  # int32[n]  parent join-key values, sorted; dups INT32_MAX
    ssubj: jnp.ndarray  # int32[n]  parent subject values, co-sorted; -1 = dup


class ProbeResult(NamedTuple):
    subjects: jnp.ndarray   # int32[m, max_matches]  parent subjects (or junk)
    valid: jnp.ndarray      # bool[m, max_matches]
    truncated: jnp.ndarray  # bool[]  some span exceeded max_matches


def _lexsort_pairs(keys: jnp.ndarray, subjects: jnp.ndarray):
    """Stable sort by (key, subject): two stable argsorts."""
    o1 = jnp.argsort(subjects, stable=True)
    k1, s1 = keys[o1], subjects[o1]
    o2 = jnp.argsort(k1, stable=True)
    return k1[o2], s1[o2]


def _mask_dups(skeys: jnp.ndarray, ssubj: jnp.ndarray) -> jnp.ndarray:
    """After lexsort, mask repeated (key, subject) pairs (set semantics)."""
    prev_same = jnp.concatenate(
        [
            jnp.array([False]),
            (skeys[1:] == skeys[:-1]) & (ssubj[1:] == ssubj[:-1]),
        ]
    )
    return jnp.where(prev_same, _SUBJ_MASKED, ssubj)


def _compact(skeys: jnp.ndarray, ssubj: jnp.ndarray) -> PJTTSorted:
    """Move each masked pair out of its key's span: a stable partition of
    the sorted pairs, kept ones first, the masked ones after them under the
    key ``INT32_MAX`` — what a stable re-sort by key gives, without a sort.
    The kept pairs stay in (key, subject) order and the length stays n."""
    keep = ssubj != _SUBJ_MASKED
    n_keep = jnp.sum(keep, dtype=jnp.int32)
    dest = jnp.where(
        keep,
        jnp.cumsum(keep, dtype=jnp.int32) - 1,
        n_keep + jnp.cumsum(~keep, dtype=jnp.int32) - 1,
    )
    return PJTTSorted(
        skeys=jnp.zeros_like(skeys).at[dest].set(
            jnp.where(keep, skeys, _I32_MAX), unique_indices=True
        ),
        ssubj=jnp.zeros_like(ssubj).at[dest].set(ssubj, unique_indices=True),
    )


def build_sorted(keys: jnp.ndarray, subjects: jnp.ndarray) -> PJTTSorted:
    """Build the PJTT from parent rows.  Cost: one sort —
    the paper's |N_parent| build term — and one linear compaction."""
    # a pair with a NULL key or subject is masked like a repeated pair,
    # so it lands in the INT32_MAX tail and never matches
    subjects = jnp.where(keys == NULL_ID, _SUBJ_MASKED, subjects)
    skeys, ssubj = _lexsort_pairs(keys, subjects)
    return _compact(skeys, _mask_dups(skeys, ssubj))


def probe_sorted(
    pjtt: PJTTSorted, child_keys: jnp.ndarray, max_matches: int
) -> ProbeResult:
    start = jnp.searchsorted(pjtt.skeys, child_keys, side="left")
    end = jnp.searchsorted(pjtt.skeys, child_keys, side="right")
    return _expand_spans(pjtt.ssubj, start, end - start, max_matches)


def _expand_spans(
    ssubj: jnp.ndarray, start: jnp.ndarray, count: jnp.ndarray, max_matches: int
) -> ProbeResult:
    """Expand [start, start+count) spans into a padded (m, K) block."""
    n = ssubj.shape[0]
    offs = jnp.arange(max_matches, dtype=jnp.int32)[None, :]
    idx = start[:, None].astype(jnp.int32) + offs
    within = offs < count[:, None]
    subjects = ssubj[jnp.clip(idx, 0, n - 1)]
    valid = within & (subjects != _SUBJ_MASKED)
    truncated = jnp.any(count > max_matches)
    return ProbeResult(subjects=subjects, valid=valid, truncated=truncated)


# a ragged step's lanes are its candidates rounded up to a multiple of an
# eighth of their next power of two, and of at least 1024: padding stays
# under a quarter of the candidates (under 1024 lanes for a small step),
# and the candidate counts of one doubling share at most four shapes
_MIN_LANES = 1024
_LANE_STEPS = 8


def spans(skeys: np.ndarray, child_keys: np.ndarray) -> "tuple[np.ndarray, np.ndarray]":
    """Each child key's ``[start, start + count)`` span in a host copy of
    a PJTT's sorted keys: the key's distinct parent subjects.  The keys
    are searched in sorted order, so the searches walk ``skeys`` forward
    instead of jumping about it."""
    order = np.argsort(child_keys)
    needles = child_keys[order]
    lo = np.searchsorted(skeys, needles, side="left")
    hi = np.searchsorted(skeys, needles, side="right")
    start = np.empty_like(lo)
    count = np.empty_like(lo)
    start[order] = lo
    count[order] = hi - lo
    return start, count


def lane_bucket(n_cand: int) -> int:
    """The lanes of a step over ``n_cand`` candidates: ``n_cand`` rounded
    up to a multiple of ``max(1024, next_pow2(n_cand) // 8)``."""
    step = max(_MIN_LANES, next_pow2(n_cand) // _LANE_STEPS)
    return -(-n_cand // step) * step


def ragged_lanes(
    skeys: np.ndarray, child_keys: np.ndarray, live: np.ndarray
) -> "tuple[np.ndarray, np.ndarray, int]":
    """A batch's candidates one lane each: the ``live`` child rows' spans
    in a host copy of a PJTT's sorted keys, in row-major ``(row, k)``
    order.  Lane ``j`` below ``n_cand`` pairs child row ``rows[j]`` with
    sorted position ``pos[j]``; the lanes after them, up to
    ``lane_bucket(n_cand)``, point at row 0 and position 0.  Returns
    ``(rows, pos, n_cand)``."""
    start, count = spans(skeys, child_keys)
    count = np.where(live, count, 0)
    n_cand = int(count.sum())
    n_lanes = lane_bucket(n_cand)
    row = np.repeat(np.arange(len(count), dtype=np.int32), count)
    first = np.cumsum(count) - count  # each row's first lane
    rows = np.zeros(n_lanes, np.int32)
    pos = np.zeros(n_lanes, np.int32)
    rows[:n_cand] = row
    pos[:n_cand] = start[row] - first[row] + np.arange(n_cand)
    return rows, pos, n_cand
