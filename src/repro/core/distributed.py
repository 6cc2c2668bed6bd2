"""Distributed PTT/PJTT — the paper's operators at pod scale.

The hash space is the shard axis (DESIGN.md §4): a triple's *owner* device is
a hash of its 64-bit key, so every device holds a disjoint slice of the PTT
and duplicate elimination is exact with no cross-device races.  The shuffle is
one ``all_to_all`` of int32/uint32 key traffic (tiny next to model training
collectives) followed by a purely local batched insert, plus a second
``all_to_all`` to route the ``is_new`` verdicts back to the producers — the
classic shuffle-join/shuffle-dedup of distributed query engines, expressed in
``shard_map``.

The same shuffle machinery distributes the PJTT: parent (key, subject) pairs
are shuffled by join-key owner, each shard builds a local sorted index, and
OJM probes are shuffled to the owner and answered in place.

All functions are written against an arbitrary axis-name tuple so they run
unchanged on the single-pod ``("data", "model")`` and multi-pod
``("pod", "data", "model")`` production meshes.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import hashing, hashset, pjtt
from repro.core.hashing import EMPTY

# Default slack factor for the fixed-capacity all_to_all bins.  With random
# hash owners the per-bucket load is Binomial(n_local, 1/S); 4x the mean keeps
# the overflow probability negligible for n_local >= 1k.
BIN_SLACK = 4


class ShardedPTT(NamedTuple):
    """PTT whose rows are sharded across every mesh axis (axis 0)."""

    hi: jnp.ndarray  # uint32[n_shards, cap_per_shard]
    lo: jnp.ndarray  # uint32[n_shards, cap_per_shard]


def make_sharded_ptt(mesh, capacity_total: int) -> ShardedPTT:
    n_shards = mesh.devices.size
    cap = hashset.next_pow2(max(capacity_total // n_shards, 8))
    spec = P(tuple(mesh.axis_names))
    shaped = jax.ShapeDtypeStruct((n_shards, cap), jnp.uint32)
    init = jax.jit(
        lambda: jnp.full(shaped.shape, EMPTY, jnp.uint32),
        out_shardings=NamedSharding(mesh, spec),
    )
    return ShardedPTT(hi=init(), lo=init())


def _owner(key_hi: jnp.ndarray, key_lo: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Owner shard of a key.  Uses a re-mixed lane so the owner bits are
    independent of the slot bits (key_lo & mask) used inside the local table."""
    return (
        hashing.fmix32(key_hi ^ jnp.uint32(0xA5A5A5A5)) % jnp.uint32(n_shards)
    ).astype(jnp.int32)


def _bin_by_owner(owner, n_shards: int, cap: int, valid):
    """Group lane indices by owner into an (n_shards, cap) routing plan.

    Returns (dest_slot[n] int32 with -1 for overflow/invalid, send_index
    [n_shards*cap] int32 gather map with -1 for empty, overflow flag).
    """
    n = owner.shape[0]
    owner_v = jnp.where(valid, owner, n_shards)  # invalid -> virtual bucket
    order = jnp.argsort(owner_v, stable=True)
    sorted_owner = owner_v[order]
    starts = jnp.searchsorted(sorted_owner, jnp.arange(n_shards + 1, dtype=owner.dtype))
    rank = jnp.arange(n, dtype=jnp.int32) - starts[sorted_owner].astype(jnp.int32)
    ok = (sorted_owner < n_shards) & (rank < cap)
    dest = jnp.where(ok, sorted_owner.astype(jnp.int32) * cap + rank, -1)
    # scatter original lane index into the send buffer
    send_index = jnp.full((n_shards * cap,), -1, dtype=jnp.int32)
    send_index = send_index.at[jnp.where(ok, dest, n_shards * cap)].set(
        order.astype(jnp.int32), mode="drop"
    )
    overflow = jnp.any((sorted_owner < n_shards) & (rank >= cap))
    # dest per ORIGINAL lane (for the route-back un-permute)
    dest_by_lane = jnp.full((n,), -1, jnp.int32).at[order].set(dest)
    return dest_by_lane, send_index, overflow


def _gather_or(x, idx, fill):
    safe = jnp.clip(idx, 0, x.shape[0] - 1)
    return jnp.where(idx >= 0, x[safe], fill)


def distributed_insert(mesh, table: ShardedPTT, key_hi, key_lo, valid):
    """Shuffle-dedup: batched distributed PTT insert.

    ``key_hi/key_lo/valid`` are sharded over axis 0 across the whole mesh
    (one slice per device).  Returns (table', is_new, overflow) with ``is_new``
    aligned to the input layout.  Exactly-one-winner semantics hold globally
    because each key is judged only by its owner shard.
    """
    axes = tuple(mesh.axis_names)
    n_shards = mesh.devices.size

    def fn(thi, tlo, khi, klo, val):
        # local shapes: thi (1, cap_t), khi (n_local,)
        thi, tlo = thi[0], tlo[0]
        khi, klo, val = khi, klo, val
        n_local = khi.shape[0]
        cap = max(BIN_SLACK * ((n_local + n_shards - 1) // n_shards), 1)
        owner = _owner(khi, klo, n_shards)
        dest_by_lane, send_index, ovf_bin = _bin_by_owner(owner, n_shards, cap, val)

        send_hi = _gather_or(khi, send_index, jnp.uint32(EMPTY)).reshape(n_shards, cap)
        send_lo = _gather_or(klo, send_index, jnp.uint32(EMPTY)).reshape(n_shards, cap)

        recv_hi = jax.lax.all_to_all(send_hi, axes, 0, 0).reshape(-1)
        recv_lo = jax.lax.all_to_all(send_lo, axes, 0, 0).reshape(-1)
        recv_valid = ~((recv_hi == jnp.uint32(EMPTY)) & (recv_lo == jnp.uint32(EMPTY)))

        res = hashset.insert_masked(
            hashset.HashSet(thi, tlo), recv_hi, recv_lo, recv_valid
        )
        flags = res.is_new.reshape(n_shards, cap)
        flags_back = jax.lax.all_to_all(flags, axes, 0, 0).reshape(-1)
        # un-permute: lane i sent to flat slot dest_by_lane[i]
        is_new = _gather_or(flags_back, dest_by_lane, False) & val
        ovf = res.overflowed | ovf_bin
        ovf_global = jax.lax.pmax(ovf.astype(jnp.int32), axes) > 0
        return res.table.hi[None], res.table.lo[None], is_new, ovf_global

    spec_t = P(axes)
    spec_b = P(axes)
    out = jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            check_vma=False,
            in_specs=(spec_t, spec_t, spec_b, spec_b, spec_b),
            out_specs=(spec_t, spec_t, spec_b, P()),
        )
    )(table.hi, table.lo, key_hi, key_lo, valid)
    thi, tlo, is_new, ovf = out
    return ShardedPTT(hi=thi, lo=tlo), is_new, ovf


class ShardedPJTT(NamedTuple):
    """Per-shard sorted join index over owner-shuffled parent pairs."""

    skeys: jnp.ndarray  # int32[n_shards, cap]   sorted within shard, -1 pad at END
    ssubj: jnp.ndarray  # int32[n_shards, cap]


_PAD_KEY = np.int32(2147483647)  # sorts to the end; never a dictionary id


def build_distributed_pjtt(mesh, parent_keys, parent_subjects):
    """Shuffle parent (key, subject) pairs to their key's owner shard and
    build a local sorted index there.  Bin overflow is reported (skewed keys
    beyond BIN_SLACK× the mean load need a larger slack)."""
    axes = tuple(mesh.axis_names)
    n_shards = mesh.devices.size

    def fn(pk, ps):
        n_local = pk.shape[0]
        valid = pk >= 0
        hi, lo = hashing.mix64([pk])
        owner = _owner(hi, lo, n_shards)
        cap = max(BIN_SLACK * ((n_local + n_shards - 1) // n_shards), 1)
        dest_by_lane, send_index, ovf_bin = _bin_by_owner(owner, n_shards, cap, valid)
        send_k = _gather_or(pk, send_index, _PAD_KEY).reshape(n_shards, cap)
        send_s = _gather_or(ps, send_index, jnp.int32(-1)).reshape(n_shards, cap)
        recv_k = jax.lax.all_to_all(send_k, axes, 0, 0).reshape(-1)
        recv_s = jax.lax.all_to_all(send_s, axes, 0, 0).reshape(-1)
        idx = pjtt.build_sorted(recv_k, recv_s)
        ovf = jax.lax.pmax(ovf_bin.astype(jnp.int32), axes) > 0
        return idx.skeys[None], idx.ssubj[None], ovf

    spec_b = P(axes)
    skeys, ssubj, ovf = jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            check_vma=False,
            in_specs=(spec_b, spec_b),
            out_specs=(spec_b, spec_b, P()),
        )
    )(parent_keys, parent_subjects)
    return ShardedPJTT(skeys=skeys, ssubj=ssubj), ovf


def distributed_ojm_probe(mesh, index: ShardedPJTT, child_keys, max_matches: int):
    """Index-join probe against the distributed PJTT.

    Child keys are shuffled to their owner shard, answered with a padded
    (cap, max_matches) block, and routed back.  Returns (subjects, valid,
    overflow) aligned with the child layout: int32[n, max_matches].
    """
    axes = tuple(mesh.axis_names)
    n_shards = mesh.devices.size

    def fn(sk, ss, ck):
        sk, ss = sk[0], ss[0]
        n_local = ck.shape[0]
        valid = ck >= 0
        hi, lo = hashing.mix64([ck])
        owner = _owner(hi, lo, n_shards)
        cap = max(BIN_SLACK * ((n_local + n_shards - 1) // n_shards), 1)
        dest_by_lane, send_index, ovf_bin = _bin_by_owner(owner, n_shards, cap, valid)
        send_k = _gather_or(ck, send_index, _PAD_KEY).reshape(n_shards, cap)
        recv_k = jax.lax.all_to_all(send_k, axes, 0, 0).reshape(-1)

        # manual span probe: pad probes (and the index's own pad rows, which
        # share _PAD_KEY and so form one huge span) must not count as matches
        # or trigger the truncation flag
        real = recv_k != _PAD_KEY
        s0 = jnp.searchsorted(sk, recv_k, side="left")
        e0 = jnp.searchsorted(sk, recv_k, side="right")
        cnt = jnp.where(real, e0 - s0, 0)
        pr = pjtt._expand_spans(ss, s0, cnt, max_matches)
        trunc = jnp.any(cnt > max_matches)
        subj = jnp.where(pr.valid, pr.subjects, -1)
        subj_back = jax.lax.all_to_all(
            subj.reshape(n_shards, cap, max_matches), axes, 0, 0
        ).reshape(-1, max_matches)
        safe = jnp.clip(dest_by_lane, 0, subj_back.shape[0] - 1)
        out_subj = jnp.where(dest_by_lane[:, None] >= 0, subj_back[safe], -1)
        out_valid = (out_subj >= 0) & valid[:, None]
        ovf = jax.lax.pmax((ovf_bin | trunc).astype(jnp.int32), axes) > 0
        return out_subj, out_valid, ovf

    spec_b = P(axes)
    subs, vals, ovf = jax.jit(
        jax.shard_map(
            fn,
            mesh=mesh,
            check_vma=False,
            in_specs=(spec_b, spec_b, spec_b),
            out_specs=(spec_b, spec_b, P()),
        )
    )(index.skeys, index.ssubj, child_keys)
    return subs, vals, ovf
