"""Vocab-parallel embedding + logits (Megatron pattern, explicit shard_map).

GSPMD lowers ``jnp.take`` on a vocab-sharded table to an all-gather of the
WHOLE table (measured: 6 GiB/device for command-r's 256k x 12288 table), so
the gather is written explicitly:

  storage   : table (V, d) sharded P('model', 'data')  — vocab over TP,
              embedding dim over DP (FSDP-style, spreads optimizer state)
  embed     : all-gather d-shards over 'data' (transient V/16 x d slice)
              -> masked local take -> psum over 'model'
  logits    : h @ slice^T per model shard -> (B, S, V/16) vocab-sharded
              logits, exactly what the sharded softmax loss wants

Token streams are flattened to (B*S,) and sharded over the dp axes, so any
batch/wave shape whose token count divides the dp product works (chunked
prefill waves, microbatches); tiny decode batches fall back to a replicated
id stream (traffic is negligible there).  Falls back to plain dense ops
when no mesh is active, so smoke tests and CPU examples run unchanged.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.sharding import active_axes


def _mesh_ready() -> bool:
    axes = active_axes()
    return "model" in axes and "data" in axes


def _dp_axes() -> tuple:
    return tuple(a for a in active_axes() if a in ("pod", "data"))


def _dp_prod(mesh, dp) -> int:
    return int(np.prod([mesh.shape[a] for a in dp])) if dp else 1


def embed(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """(V, d) table, (B, S) int ids -> (B, S, d)."""
    if not _mesh_ready():
        return jnp.take(table, ids, axis=0)
    mesh = jax.sharding.get_abstract_mesh()
    n_model = mesh.shape["model"]
    dp = _dp_axes()
    V = table.shape[0]
    v_loc = V // n_model
    b, s = ids.shape
    flat = ids.reshape(-1)
    if flat.shape[0] % _dp_prod(mesh, dp) == 0:
        ids_spec, out_spec = P(dp), P(dp, None)
    else:  # tiny decode batches: replicate the id stream
        ids_spec, out_spec = P(None), P(None, None)

    def fn(tbl, ids_l):
        # tbl: (V/model, d/data); gather the d-shards (FSDP use-gather)
        full = jax.lax.all_gather(tbl, "data", axis=1, tiled=True)
        idx = jax.lax.axis_index("model")
        lo = idx * v_loc
        local = ids_l - lo
        ok = (local >= 0) & (local < v_loc)
        rows = jnp.take(full, jnp.clip(local, 0, v_loc - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, 0)
        return jax.lax.psum(rows, "model")

    out = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P("model", "data"), ids_spec),
        out_specs=out_spec,
        check_vma=False,
    )(table, flat)
    return out.reshape(b, s, table.shape[1])


def tied_logits(table: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """(V, d) table, (B, S, d) hidden -> (B, S, V) logits, vocab-sharded on
    'model' (ready for the sharded-softmax loss)."""
    if not _mesh_ready():
        return h @ table.T
    mesh = jax.sharding.get_abstract_mesh()
    dp = _dp_axes()
    b, s, d = h.shape
    flat = h.reshape(-1, d)
    if flat.shape[0] % _dp_prod(mesh, dp) == 0:
        h_spec, out_spec = P(dp, None), P(dp, "model")
    else:
        h_spec, out_spec = P(None, None), P(None, "model")

    def fn(tbl, h_l):
        full = jax.lax.all_gather(tbl, "data", axis=1, tiled=True)  # (V/m, d)
        return h_l @ full.T  # (n/dp, V/m)

    out = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P("model", "data"), h_spec),
        out_specs=out_spec,
        check_vma=False,
    )(table, flat)
    return out.reshape(b, s, table.shape[0])
