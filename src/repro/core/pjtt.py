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
A span holds each distinct ``(key, subject)`` pair once, as the paper's PJTT
maps a value to a *set* of subjects: repeated pairs are masked with a ``-1``
subject and moved out of their key's span to a tail under the key
``INT32_MAX``, which is never a dictionary id.  So a key's span count is its
number of distinct subjects, and that is the probe width it needs.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.core.hashset import next_pow2
from repro.data.encoder import NULL_ID

MAX_PROBE_ROUNDS = 64
_KEY_EMPTY = np.int32(-1)  # join keys are dictionary ids >= 0
_SUBJ_MASKED = np.int32(NULL_ID)  # a repeated pair, or a NULL subject
_I32_MAX = jnp.iinfo(jnp.int32).max


class PJTTSorted(NamedTuple):
    skeys: jnp.ndarray  # int32[n]  parent join-key values, sorted; dups INT32_MAX
    ssubj: jnp.ndarray  # int32[n]  parent subject values, co-sorted; -1 = dup


class PJTTHash(NamedTuple):
    tkey: jnp.ndarray    # int32[cap]  join-key or -1 (empty)
    tstart: jnp.ndarray  # int32[cap]  span start into ssubj
    tcount: jnp.ndarray  # int32[cap]  span length: distinct subjects
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
    """Build the sorted-strategy PJTT from parent rows.  Cost: one sort —
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


def build_hash(keys: jnp.ndarray, subjects: jnp.ndarray) -> PJTTHash:
    """Build the hash-strategy PJTT: group via sort, then insert each unique
    key with its (start, count) span into an open-addressing map."""
    return hash_index(build_sorted(keys, subjects))


def hash_index(srt: PJTTSorted) -> PJTTHash:
    """The hash-strategy PJTT over a built sorted one: each key's span of
    distinct subjects, as ``(start, count)`` in an open-addressing map."""
    skeys, ssubj = srt
    n = skeys.shape[0]
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
