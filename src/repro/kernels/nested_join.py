"""Pallas kernel: blocked nested-loop join (the paper's naive OJM baseline).

All-pairs equality join between child and parent join keys, shaped like a
GEMM: a block of child keys stays resident in VMEM while parent tiles are
streamed through the second grid dimension.  Matched parent subjects are
packed left-to-right (parent order) into a padded (m, K) output — the same
padded-ragged layout as ``pjtt.probe_sorted``, so the two are interchangeable.

Grid: (child_blocks, parent_tiles); parent tiles iterate innermost, so the
output block and the per-row fill cursor act as sequential accumulators
(revision pattern: out index_map ignores the tile dim).

Comparisons = |child| × |parent| — the Θ(N_parent·N_child) the paper ascribes
to the naive engine; the kernel merely executes it at peak, it cannot beat
the PJTT's asymptotics (that is the paper's whole point).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_M = 256
BLOCK_N = 1024
_PAD = -1  # python int: Pallas kernels may not capture traced constants


def _kernel(ck_ref, pk_ref, ps_ref, out_ref, cnt_ref, *, max_matches: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref[...], jnp.int32(_PAD))
        cnt_ref[...] = jnp.zeros_like(cnt_ref[...])

    ck = ck_ref[...]          # (bm,)
    pk = pk_ref[...]          # (bn,)
    ps = ps_ref[...]          # (bn,)
    bm, bn = ck.shape[0], pk.shape[0]
    K = max_matches

    eq = ck[:, None] == pk[None, :]               # (bm, bn) all-pairs compare
    rank = jnp.cumsum(eq, axis=1) - 1              # match rank within tile
    cur = cnt_ref[...]                             # (bm,) fill cursor
    col = cur[:, None] + rank
    write = eq & (col >= 0) & (col < K)

    out = out_ref[...]
    rows = jnp.broadcast_to(jnp.arange(bm)[:, None], (bm, bn))
    cols = jnp.where(write, col, K)                # K -> dropped
    out = out.at[rows, cols].set(
        jnp.broadcast_to(ps[None, :], (bm, bn)), mode="drop"
    )
    out_ref[...] = out
    cnt_ref[...] = cur + jnp.sum(eq, axis=1, dtype=jnp.int32)


def nested_join(
    parent_keys: jnp.ndarray,      # int32[n]  (>= 0; -1 reserved for padding)
    parent_subjects: jnp.ndarray,  # int32[n]
    child_keys: jnp.ndarray,       # int32[m]
    max_matches: int,
    block_m: int = BLOCK_M,
    block_n: int = BLOCK_N,
    interpret: bool = True,
):
    """Returns (subjects int32[m, K], valid bool[m, K], truncated bool[])."""
    n = parent_keys.shape[0]
    m = child_keys.shape[0]
    pad_m = (-m) % block_m
    pad_n = (-n) % block_n
    ck = jnp.pad(child_keys, (0, pad_m), constant_values=-1)
    pk = jnp.pad(parent_keys, (0, pad_n), constant_values=-1)
    ps = jnp.pad(parent_subjects, (0, pad_n), constant_values=-1)
    grid = (ck.shape[0] // block_m, pk.shape[0] // block_n)

    subjects, counts = pl.pallas_call(
        lambda *refs: _kernel(*refs, max_matches=max_matches),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m,), lambda i, j: (i,)),
            pl.BlockSpec((block_n,), lambda i, j: (j,)),
            pl.BlockSpec((block_n,), lambda i, j: (j,)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, max_matches), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ck.shape[0], max_matches), jnp.int32),
            jax.ShapeDtypeStruct((ck.shape[0],), jnp.int32),
        ],
        interpret=interpret,
    )(ck, pk, ps)

    subjects = subjects[:m]
    counts = counts[:m]
    offs = jnp.arange(max_matches, dtype=jnp.int32)[None, :]
    valid = (offs < counts[:, None]) & (subjects != jnp.int32(_PAD))
    truncated = jnp.any(counts > max_matches)
    return subjects, valid, truncated
