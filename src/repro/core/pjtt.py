"""Predicate Join Tuple Table — the paper's index-join structure, TPU-native.

The paper's PJTT maps ``value(join condition B) -> {subjects of the parent
triples map}`` so that an Object Join Map becomes an index join (one probe per
child row) instead of a nested-loop join.

Join keys and subjects are dictionary-encoded int32 term-value ids (see
``repro.data.encoder``), so the structure is built from flat int32 arrays:
the parent's ``(key, subject)`` pairs sorted once, and a probe is a pair of
``searchsorted`` calls yielding a ``[start, end)`` span — sequential access,
one sort to build.

A probe comes back in a *padded-ragged* layout: ``(m, max_matches)`` subject
ids plus a validity mask — the TPU-native encoding of the N-M join output.
A span holds each distinct ``(key, subject)`` pair once, as the paper's PJTT
maps a value to a *set* of subjects: repeated pairs are masked with a ``-1``
subject and moved out of their key's span to a tail under the key
``INT32_MAX``, which is never a dictionary id.  So a key's span count is its
number of distinct subjects, and that is the probe width it needs.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

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
