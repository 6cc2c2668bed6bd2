"""Predicate Join Tuple Table — the paper's index-join structure, TPU-native.

The paper's PJTT maps ``value(join condition B) -> {subjects of the parent
triples map}`` so that an Object Join Map becomes an index join (one probe per
child row) instead of a nested-loop join.

Join keys and subjects are dictionary-encoded int32 term-value ids (see
``repro.data.encoder``), so the structure is built from flat int32 arrays.
Two interchangeable physical strategies (DESIGN.md §6):

* **sorted** — sort parent ``(key, subject)`` pairs once; a probe is a pair of
  ``searchsorted`` calls yielding a ``[start, end)`` span.  Sequential-access
  friendly; the default on TPU.
* **hash** — an open-addressing int32 map ``key -> (start, count)`` into the
  same sorted subjects array; a probe is an O(1) double-hash loop.

Both return probes in a *padded-ragged* layout: ``(m, max_matches)`` subject
ids plus a validity mask — the TPU-native encoding of the N-M join output.
Duplicate parent ``(key, subject)`` pairs are kept in the span but masked with
a ``-1`` subject so the PJTT behaves as the paper's set semantics.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.core.hashset import next_pow2

MAX_PROBE_ROUNDS = 64
_KEY_EMPTY = np.int32(-1)  # join keys are dictionary ids >= 0
_SUBJ_MASKED = np.int32(-1)
_I32_MAX = jnp.iinfo(jnp.int32).max


class PJTTSorted(NamedTuple):
    skeys: jnp.ndarray  # int32[n]  parent join-key values, sorted
    ssubj: jnp.ndarray  # int32[n]  parent subject values, co-sorted; -1 = dup


class PJTTHash(NamedTuple):
    tkey: jnp.ndarray    # int32[cap]  join-key or -1 (empty)
    tstart: jnp.ndarray  # int32[cap]  span start into ssubj
    tcount: jnp.ndarray  # int32[cap]  span length
    ssubj: jnp.ndarray   # int32[n]    sorted subjects; -1 = dup


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


def build_sorted(keys: jnp.ndarray, subjects: jnp.ndarray) -> PJTTSorted:
    """Build the sorted-strategy PJTT from parent rows.  Cost: one sort —
    the paper's |N_parent| build term."""
    skeys, ssubj = _lexsort_pairs(keys, subjects)
    return PJTTSorted(skeys=skeys, ssubj=_mask_dups(skeys, ssubj))


def probe_sorted(
    pjtt: PJTTSorted, child_keys: jnp.ndarray, max_matches: int
) -> ProbeResult:
    start = jnp.searchsorted(pjtt.skeys, child_keys, side="left")
    end = jnp.searchsorted(pjtt.skeys, child_keys, side="right")
    return _expand_spans(pjtt.ssubj, start, end - start, max_matches)


def build_hash(keys: jnp.ndarray, subjects: jnp.ndarray) -> PJTTHash:
    """Build the hash-strategy PJTT: group via sort, then insert each unique
    key with its (start, count) span into an open-addressing map."""
    n = keys.shape[0]
    skeys, ssubj0 = _lexsort_pairs(keys, subjects)
    ssubj = _mask_dups(skeys, ssubj0)

    is_start = jnp.concatenate([jnp.array([True]), skeys[1:] != skeys[:-1]])
    seg_id = jnp.cumsum(is_start) - 1  # group index per sorted row
    counts_per_seg = jax.ops.segment_sum(
        jnp.ones((n,), dtype=jnp.int32), seg_id, num_segments=n
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    lane_count = counts_per_seg[seg_id]

    cap = next_pow2(int(n / 0.7) + 1)
    tkey = jnp.full((cap,), _KEY_EMPTY, dtype=jnp.int32)
    tstart = jnp.zeros((cap,), dtype=jnp.int32)
    tcount = jnp.zeros((cap,), dtype=jnp.int32)

    hi, lo = hashing.mix64([skeys])
    maskc = jnp.uint32(cap - 1)
    base = lo & maskc
    step = ((hi | jnp.uint32(1)) & maskc) | jnp.uint32(1)

    class _S(NamedTuple):
        tkey: jnp.ndarray
        tstart: jnp.ndarray
        tcount: jnp.ndarray
        done: jnp.ndarray
        rnd: jnp.ndarray

    def cond(s: _S):
        return (~jnp.all(s.done)) & (s.rnd < MAX_PROBE_ROUNDS)

    def body(s: _S) -> _S:
        slot = ((base + s.rnd.astype(jnp.uint32) * step) & maskc).astype(jnp.int32)
        occ = s.tkey[slot]
        active = ~s.done
        empty = active & (occ == _KEY_EMPTY)
        claim = jnp.full((cap,), _I32_MAX, dtype=jnp.int32)
        claim = claim.at[jnp.where(empty, slot, cap)].min(
            jnp.where(empty, pos, _I32_MAX), mode="drop"
        )
        won = empty & (claim[slot] == pos)
        nkey = s.tkey.at[jnp.where(won, slot, cap)].set(skeys, mode="drop")
        nstart = s.tstart.at[jnp.where(won, slot, cap)].set(pos, mode="drop")
        ncount = s.tcount.at[jnp.where(won, slot, cap)].set(lane_count, mode="drop")
        # keys are unique among active lanes (only span starts are active),
        # so no same-key twin handling is needed here.
        return _S(nkey, nstart, ncount, s.done | won, s.rnd + 1)

    init = _S(tkey, tstart, tcount, ~is_start, jnp.int32(0))
    out = jax.lax.while_loop(cond, body, init)
    return PJTTHash(tkey=out.tkey, tstart=out.tstart, tcount=out.tcount, ssubj=ssubj)


def probe_hash(
    pjtt: PJTTHash, child_keys: jnp.ndarray, max_matches: int
) -> ProbeResult:
    cap = pjtt.tkey.shape[0]
    m = child_keys.shape[0]
    hi, lo = hashing.mix64([child_keys])
    maskc = jnp.uint32(cap - 1)
    base = lo & maskc
    step = ((hi | jnp.uint32(1)) & maskc) | jnp.uint32(1)

    class _S(NamedTuple):
        done: jnp.ndarray
        start: jnp.ndarray
        cnt: jnp.ndarray
        rnd: jnp.ndarray

    def cond(s: _S):
        return (~jnp.all(s.done)) & (s.rnd < MAX_PROBE_ROUNDS)

    def body(s: _S) -> _S:
        slot = ((base + s.rnd.astype(jnp.uint32) * step) & maskc).astype(jnp.int32)
        occ = pjtt.tkey[slot]
        active = ~s.done
        hit = active & (occ == child_keys)
        empty = active & (occ == _KEY_EMPTY)
        return _S(
            done=s.done | hit | empty,
            start=jnp.where(hit, pjtt.tstart[slot], s.start),
            cnt=jnp.where(hit, pjtt.tcount[slot], s.cnt),
            rnd=s.rnd + 1,
        )

    init = _S(
        done=jnp.zeros((m,), dtype=bool),
        start=jnp.zeros((m,), dtype=jnp.int32),
        cnt=jnp.zeros((m,), dtype=jnp.int32),
        rnd=jnp.int32(0),
    )
    out = jax.lax.while_loop(cond, body, init)
    return _expand_spans(pjtt.ssubj, out.start, out.cnt, max_matches)


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
