"""Wide & Deep (Cheng et al., arXiv:1606.07792) — the assigned recsys arch.

40 sparse fields, embed_dim 32, deep MLP 1024-512-256, concat interaction.
The embedding LOOKUP is the hot path (assignment note): JAX has no
EmbeddingBag, so it is built here from ``jnp.take`` + ``segment_sum``, with
the paper-derived ``dedup_gather`` as a first-class optimization for
duplicate-heavy id streams (DESIGN.md §5).

Sharding: the stacked embedding table (F, V, D) and the wide table (F, V)
are row-sharded over ('data','model') on the vocab axis; the MLP is
replicated; the batch is sharded over ('pod','data').
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.dedup_gather import gather_maybe_dedup
from repro.models import layers
from repro.models.sharding import active_axes


@dataclasses.dataclass(frozen=True)
class WideDeepConfig:
    name: str = "wide-deep"
    n_sparse: int = 40
    embed_dim: int = 32
    vocab_per_field: int = 1_000_000
    n_dense: int = 13
    mlp: tuple[int, ...] = (1024, 512, 256)
    # multi-hot bag size per field (1 = one-hot); EmbeddingBag sums the bag
    bag_size: int = 1
    dedup_cap: int | None = None  # PTT-style unique-gather cap (None = off)
    dtype: Any = jnp.float32


def init(key, cfg: WideDeepConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    F, V, D = cfg.n_sparse, cfg.vocab_per_field, cfg.embed_dim
    d_in = F * D + cfg.n_dense
    return {
        "embed": jax.random.normal(k1, (F, V, D), cfg.dtype) * 0.01,
        "wide": jax.random.normal(k2, (F, V), cfg.dtype) * 0.01,
        "mlp": layers.mlp_init(k3, (d_in, *cfg.mlp), cfg.dtype),
        "head": layers.dense_init(k4, cfg.mlp[-1], 1, cfg.dtype, bias=True),
    }


def param_specs(cfg: WideDeepConfig):
    mlp_specs = {
        f"fc{i}": {"w": P(None, None), "b": P(None)} for i in range(len(cfg.mlp))
    }
    return {
        # vocab over 'model' only: the shard_map lookup needs the full row
        # range per model shard (335 MB/device for 40 x 2^20 x 32 fp32)
        "embed": P(None, "model", None),
        "wide": P(None, "model"),
        "mlp": mlp_specs,
        "head": {"w": P(None, None), "b": P(None)},
    }


def _local_dedup(flat_ids: jnp.ndarray, cap: int):
    """Sort-based first-occurrence dedup (the PTT combiner, local to the
    shard).  Returns (unique_ids[cap], group_of_lane[n])."""
    n = flat_ids.shape[0]
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    slot = jnp.cumsum(first) - 1
    uids = jnp.zeros((cap,), flat_ids.dtype).at[
        jnp.where(first & (slot < cap), slot, cap)
    ].set(sorted_ids, mode="drop")
    group = jnp.zeros((n,), slot.dtype).at[order].set(jnp.clip(slot, 0, cap - 1))
    return uids, group


def _vocab_parallel_rows(table3, flat_ids, cfg: WideDeepConfig, mesh, dp):
    """shard_map row fetch: table (F, V, D) vocab-sharded on 'model', ids
    sharded over dp.  Local masked take + psum('model'); with ``dedup_cap``
    the shard's id stream is deduplicated FIRST, so only |S| rows ride the
    psum (the paper's |N| -> |S| saving on the wire)."""
    V = cfg.vocab_per_field
    n_model = mesh.shape["model"]
    v_loc = V // n_model

    def body(tbl, ids):
        # tbl: (F, V/m, D); ids: (n_local,) global flat ids = f*V + v
        idx = jax.lax.axis_index("model")
        lo = idx * v_loc

        def fetch(lookup_ids):
            f = lookup_ids // V
            v = lookup_ids % V - lo
            ok = (v >= 0) & (v < v_loc)
            rows = tbl[f, jnp.clip(v, 0, v_loc - 1)]
            rows = jnp.where(ok[..., None], rows, 0)
            return jax.lax.psum(rows, "model")

        if cfg.dedup_cap is not None:
            uids, group = _local_dedup(ids, cfg.dedup_cap)
            urows = fetch(uids)              # (cap, D) — the only psum
            return jnp.take(urows, group, axis=0)
        return fetch(ids)

    import numpy as _np

    dp_prod = int(_np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if flat_ids.shape[0] % dp_prod == 0:
        ids_spec, out_spec = P(dp), P(dp, None)
    else:  # tiny batches (retrieval_cand B=1): replicate the id stream
        ids_spec, out_spec = P(None), P(None, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, "model", None), ids_spec),
        out_specs=out_spec,
        check_vma=False,
    )(table3, flat_ids)


def _fetch_rows(params_key, params, cfg: WideDeepConfig, sparse_ids):
    """(B, F, G) ids -> (B*F*G, D) rows via the vocab-parallel path when a
    mesh is active, else plain (optionally dedup'd) gather."""
    B, F, G = sparse_ids.shape
    V = cfg.vocab_per_field
    table3 = params[params_key]
    if table3.ndim == 2:  # wide table (F, V) -> (F, V, 1)
        table3 = table3[..., None]
    global_ids = (
        sparse_ids + (jnp.arange(F, dtype=sparse_ids.dtype) * V)[None, :, None]
    ).reshape(-1)
    axes = active_axes()
    if "model" in axes and "data" in axes:
        mesh = jax.sharding.get_abstract_mesh()
        dp = tuple(a for a in axes if a in ("pod", "data"))
        return _vocab_parallel_rows(table3, global_ids, cfg, mesh, dp)
    flat_table = table3.reshape(F * V, -1)
    return gather_maybe_dedup(flat_table, global_ids, cfg.dedup_cap)


def embedding_bag(params, cfg: WideDeepConfig, sparse_ids: jnp.ndarray):
    """sparse_ids int32 (B, F, bag) -> (B, F*D) summed bag embeddings.

    JAX's EmbeddingBag: row fetch + reshape-sum.  With ``dedup_cap`` set the
    id stream is deduplicated first (the PTT optimization) — one fetch (and
    one unit of cross-shard traffic) per *distinct* (field, id) pair.
    """
    B, F, G = sparse_ids.shape
    D = cfg.embed_dim
    rows = _fetch_rows("embed", params, cfg, sparse_ids)
    return rows.reshape(B, F, G, D).sum(axis=2).reshape(B, F * D)


def wide_logit(params, cfg: WideDeepConfig, sparse_ids: jnp.ndarray):
    B, F, G = sparse_ids.shape
    w = _fetch_rows("wide", params, cfg, sparse_ids)
    return w.reshape(B, F * G).sum(axis=-1)


def forward(params, cfg: WideDeepConfig, sparse_ids, dense_feats):
    """-> logits (B,).  sparse_ids (B, F, bag), dense_feats (B, n_dense)."""
    deep_in = jnp.concatenate(
        [embedding_bag(params, cfg, sparse_ids), dense_feats.astype(cfg.dtype)],
        axis=-1,
    )
    deep = layers.mlp(params["mlp"], deep_in, final_act=True)
    deep_logit = layers.dense(params["head"], deep)[:, 0]
    return deep_logit + wide_logit(params, cfg, sparse_ids)


def loss_fn(params, cfg: WideDeepConfig, sparse_ids, dense_feats, labels):
    """Binary cross-entropy (CTR objective)."""
    logits = forward(params, cfg, sparse_ids, dense_feats).astype(jnp.float32)
    y = labels.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def user_tower(params, cfg: WideDeepConfig, sparse_ids, dense_feats):
    """Deep-tower representation (B, mlp[-1]) for retrieval scoring."""
    deep_in = jnp.concatenate(
        [embedding_bag(params, cfg, sparse_ids), dense_feats.astype(cfg.dtype)],
        axis=-1,
    )
    return layers.mlp(params["mlp"], deep_in, final_act=True)


def retrieval_scores(params, cfg: WideDeepConfig, sparse_ids, dense_feats, candidates):
    """Score one query against a candidate matrix (n_cand, mlp[-1]) — a
    batched dot, NOT a loop (assignment note).  Returns (B, n_cand)."""
    u = user_tower(params, cfg, sparse_ids, dense_feats)   # (B, d)
    return u @ candidates.T.astype(u.dtype)
