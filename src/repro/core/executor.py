"""Streaming executor — the paper's engine loop, batched and jitted.

Pipeline per run (paper Fig. 2):

  RML doc --plan--> physical ops --stream--> jitted operator steps
       sources -> columnar load -> dictionary encode -> fixed-shape batches
  PTT/PJTT state threads through the jitted steps (donated buffers);
  the Knowledge Graph Creator appends the ``is_new`` triples incrementally.

Engines:
  * ``optimized`` — the SDM-RDFizer operators: PTT incremental dedup
    (``_dedup_step``) and the sorted PJTT index join (``_ojm_sorted_step``).
    One predicate runner, :meth:`Engine._run_predicates`, drives them for
    either input: eager (each op's encoded columns, cached per source and
    column set) or streamed (``stream=True``: each op's ``repro.stream``
    blocks, split into device batches).
  * ``naive``     — SDM-RDFizer⁻, the reference: generate everything,
    nested-loop joins, one merge-sort dedup per predicate at the end.

Both produce identical knowledge graphs (asserted in tests); they differ
only in operation count / wall-time, which is the paper's claim.

Instrumentation (``repro.obs``), on the thread that runs the engine:
spans ``ingest.read`` (source rows), ``ingest.encode`` (term columns to
dictionary ids), ``ingest.pjtt_build``, ``ingest.size`` (the sizing
pre-pass, and the read-back of each PJTT's sorted keys), ``ingest.step``
per batch with its children ``ingest.sync`` (every blocking read of the
step's outputs) and ``ingest.compact`` (the host-side selection of the
emitted rows), and ``ingest.assemble``; the
counters ``ingest.host_syncs`` (blocking device-to-host reads),
``ingest.replays`` (predicates replayed after a PTT overflow), and
``ingest.join_lanes`` and ``ingest.join_candidates`` (the lanes an OJM
step hashes, its candidates rounded up by ``pjtt.lane_bucket``, and the
candidates among them), and
``ingest.null_terms`` (source rows an op masks because a term it reads —
subject, object or child join key — is NULL; counted once a predicate
completes, so a replay does not count twice).  A join on two or more
conditions keys each row by the joined values of its columns, encoded in
the same dictionary as a multi-column template value (a NULL part makes
the key NULL): span ``ingest.join_key`` (args ``columns``) times each such
build, nested in the open ``ingest.*`` span, and the counter
``ingest.join_key_rows`` counts the child rows of each such join and the
parent rows of each such PJTT, once a job.  The N-Triples writer
counts ``emit.lines`` (lines written) and ``emit.terms_rendered`` (the
distinct terms it rendered, each once).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import hashing, naive, pjtt, planner
from repro.core import hashset
from repro.core.hashset import next_pow2
from repro.data import pipeline
from repro.data.encoder import NULL_ID, Dictionary, join_columns
from repro.data.sources import SourceCache
from repro.data.terms import render_terms
from repro.rml.model import MappingDocument


# --------------------------------------------------------------------------
# jitted steps (module scope: one compilation per shape, shared across ops)
# --------------------------------------------------------------------------


@partial(jax.jit, donate_argnums=(0, 1))
def _dedup_step(hi, lo, subj_tmpl, subj_vals, pred_id, obj_tmpl, obj_vals, valid):
    """SOM/ORM/CLASS step: triple keys -> masked PTT insert."""
    khi, klo = hashing.triple_key(subj_tmpl, subj_vals, pred_id, obj_tmpl, obj_vals)
    res = hashset.insert_masked(hashset.HashSet(hi, lo), khi, klo, valid)
    return res.table.hi, res.table.lo, res.is_new, res.overflowed


@partial(jax.jit, donate_argnums=(0, 1))
def _ojm_sorted_step(
    hi, lo, ssubj, subj_tmpl, subj_vals, pred_id, obj_tmpl, rows, pos, n_cand,
):
    """OJM step over a batch's ragged PJTT lanes (``pjtt.ragged_lanes``):
    lane ``j < n_cand`` joins child row ``rows[j]`` to parent subject
    ``ssubj[pos[j]]`` -> masked PTT insert.  Returns the parent subject of
    every lane with ``is_new``."""
    obj = ssubj[pos]
    valid = jnp.arange(rows.shape[0], dtype=jnp.int32) < n_cand
    khi, klo = hashing.triple_key(subj_tmpl, subj_vals[rows], pred_id, obj_tmpl, obj)
    res = hashset.insert_masked(hashset.HashSet(hi, lo), khi, klo, valid)
    return res.table.hi, res.table.lo, res.is_new, obj, res.overflowed


@jax.jit
def _naive_keys_step(subj_tmpl, subj_vals, pred_id, obj_tmpl, obj_vals):
    return hashing.triple_key(subj_tmpl, subj_vals, pred_id, obj_tmpl, obj_vals)


@partial(jax.jit, static_argnums=(2,))
def _naive_join_step(parent_keys, parent_subjects, max_matches, child_keys):
    return naive.nested_loop_join(parent_keys, parent_subjects, child_keys, max_matches)


@jax.jit
def _naive_dedup(khi, klo, valid):
    return naive.sort_dedup_masked(khi, klo, valid)


@jax.jit
def _build_sorted(keys, subjects):
    """The PJTT of one parent: each distinct ``(key, subject)`` pair once,
    so a key's span is its distinct subjects, and ``skeys`` sizes the
    joins and lays out their lanes."""
    return pjtt.build_sorted(keys, subjects)


class _JoinIndex(NamedTuple):
    """A parent's PJTT as the optimized engine's steps use it."""

    skeys: np.ndarray   # host copy of the sorted keys: spans, sizing, lanes
    ssubj: jax.Array    # the co-sorted subjects, on the device


def _join_index(pkey: str, pkeys, psubj) -> _JoinIndex:
    """Build a parent's PJTT on the device and read its sorted keys back
    once (one host sync)."""
    with obs.span("ingest.pjtt_build", pjtt=pkey):
        idx = _build_sorted(jnp.asarray(pkeys), jnp.asarray(psubj))
    with obs.span("ingest.size", pjtt=pkey):
        skeys = np.asarray(idx.skeys)
        obs.get_registry().inc("ingest.host_syncs")
    return _JoinIndex(skeys, idx.ssubj)


# --------------------------------------------------------------------------
# results
# --------------------------------------------------------------------------


@dataclasses.dataclass
class PredicateStats:
    """Per-predicate cost accounting, mirroring the paper's φ expressions."""

    kind: str
    n_candidates: int = 0   # |N_p|
    n_unique: int = 0       # |S_p|
    n_parent: int = 0
    n_child: int = 0
    n_null: int = 0         # lanes masked: a term they read is NULL

    def phi_optimized(self) -> float:
        base = self.n_candidates + 2 * self.n_unique
        if self.kind == "OJM":
            return 2 * self.n_parent + self.n_child + base
        return base

    def phi_naive(self) -> float:
        n = max(self.n_candidates, 1)
        base = self.n_candidates + self.n_unique + n * np.log2(n)
        if self.kind == "OJM":
            return self.n_parent * self.n_child + base
        return base


@dataclasses.dataclass
class KGResult:
    """The created knowledge graph, term-id form + dictionaries for decode."""

    dictionary: Dictionary
    # predicate -> dict of parallel int32 arrays
    triples: dict[str, dict[str, np.ndarray]]
    stats: dict[str, PredicateStats]
    wall_time_s: float = 0.0
    engine: str = "optimized"

    @property
    def n_triples(self) -> int:
        return sum(len(t["subj_val"]) for t in self.triples.values())

    def _term_table(self, encode: bool) -> "tuple[np.ndarray, np.ndarray]":
        """The KG's distinct ``(pattern, value)`` terms as sorted int64 keys,
        and each one rendered once (UTF-8 bytes with ``encode``)."""
        keys = np.unique(np.concatenate(
            [np.unique(_term_keys(t, side)) for t in self.triples.values()
             for side in ("subj", "obj")] or [np.zeros(0, np.int64)]
        ))
        rendered = render_terms(
            self.dictionary, keys >> 32, (keys & 0xFFFFFFFF).astype(np.int32)
        )
        obs.get_registry().inc("emit.terms_rendered", len(rendered))
        table = np.empty(len(rendered), dtype=object)
        table[:] = [r.encode("utf-8") for r in rendered] if encode else rendered
        return keys, table

    def _term_chunks(self, encode: bool):
        """``(predicate, subjects, objects)`` in chunks of at most
        ``_CHUNK_LINES`` triples: predicates in ``triples`` order, rows in
        array order, terms from :meth:`_term_table`, found by binary search,
        so no per-line array of the whole KG is held."""
        keys, table = self._term_table(encode)
        for pred, t in self.triples.items():
            for a in range(0, len(t["subj_val"]), _CHUNK_LINES):
                rows = slice(a, a + _CHUNK_LINES)
                subj = table[np.searchsorted(keys, _term_keys(t, "subj", rows))]
                obj = table[np.searchsorted(keys, _term_keys(t, "obj", rows))]
                yield pred, subj.tolist(), obj.tolist()

    def iter_ntriples(self):
        for pred, subj, obj in self._term_chunks(encode=False):
            mid = f" <{pred}> "
            for s, o in zip(subj, obj):
                yield f"{s}{mid}{o} ."

    def write_ntriples(self, path: str) -> int:
        n = 0
        with open(path, "wb") as f:
            for pred, subj, obj in self._term_chunks(encode=True):
                parts = [b"", f" <{pred}> ".encode("utf-8"), b"", b" .\n"] * len(subj)
                parts[0::4] = subj
                parts[2::4] = obj
                f.write(b"".join(parts))
                n += len(subj)
        obs.get_registry().inc("emit.lines", n)
        return n

    def sorted_ntriples(self) -> list[str]:
        """Rendered triples in sorted order — the engine-independent identity
        (dictionary ids differ between eager and streamed runs, rendered
        strings do not)."""
        return sorted(self.iter_ntriples())

    def to_store(self):
        """Servable form: a queryable, persistable ``repro.kg.TripleStore``
        built array-at-a-time over these int32 columns (works identically
        for eager and streamed runs)."""
        from repro.kg.store import TripleStore

        return TripleStore.from_kg(self.dictionary, self.triples)

    def as_set(self) -> set[tuple]:
        """Exact triple identity set (for engine-equivalence assertions)."""
        out = set()
        for pred, t in self.triples.items():
            for i in range(len(t["subj_val"])):
                out.add(
                    (
                        pred,
                        int(t["subj_pat"][i]),
                        int(t["subj_val"][i]),
                        int(t["obj_pat"][i]),
                        int(t["obj_val"][i]),
                    )
                )
        return out


def _assemble(exec_plan, triples_out) -> dict:
    """Each predicate's appended chunks as one array per column, in the op
    plan's predicate order whatever the group scheduling: the written KG
    is byte-identical planner-on/off."""
    with obs.span("ingest.assemble"):
        return {
            pred: {
                k: np.concatenate(v) if v else np.zeros(0, np.int32)
                for k, v in triples_out[pred].items()
            }
            for pred in exec_plan.by_predicate
        }


def _plan_gauges(mplan) -> None:
    """Publish the mapping plan's shape into ``repro.obs`` (plan.* rows
    in the metrics catalog)."""
    reg = obs.get_registry()
    reg.gauge("plan.groups").set(len(mplan.groups))
    reg.gauge("plan.sources").set(len(mplan.sources))
    reg.gauge("plan.shared_terms").set(len(mplan.shared))
    reg.gauge("plan.rules").set(len(mplan.exec_plan.ops))


def _sources_by_key(doc: MappingDocument) -> dict:
    """planner source_key -> LogicalSource (keys match the planned ops)."""
    return {
        planner.source_key(tm.source): tm.source
        for tm in doc.triples_maps.values()
    }


# the N-Triples writer's lines a chunk: one b"".join and one write each
_CHUNK_LINES = 1 << 16


def _term_keys(t: dict, side: str, rows: slice = slice(None)) -> np.ndarray:
    """A predicate's ``side`` ("subj" or "obj") terms as int64
    ``pattern << 32 | value`` keys."""
    pat = np.asarray(t[f"{side}_pat"][rows], np.int64)
    return (pat << 32) | (np.asarray(t[f"{side}_val"][rows], np.int64) & 0xFFFFFFFF)


_END = object()


def _spanned(name: str, blocks):
    """``blocks`` with each fetch of the next block timed as span
    ``name``; the span closes before the block is handed on, so it never
    holds a ``yield``."""
    it = iter(blocks)
    while True:
        with obs.span(name):
            block = next(it, _END)
        if block is _END:
            return
        yield block


def _touch_counters() -> None:
    """Create the engine's counters, so that a run without a sync or a
    replay reads 0 rather than nothing."""
    reg = obs.get_registry()
    for name in ("ingest.host_syncs", "ingest.replays", "ingest.join_lanes",
                 "ingest.join_candidates", "ingest.null_terms", "ingest.join_key_rows",
                 "emit.lines", "emit.terms_rendered"):
        reg.counter(name)


def _join_key(columns: tuple[str, ...]):
    """Span ``ingest.join_key`` around building a join key from two or
    more columns (their joined values and its encode); a one-column key
    opens none."""
    if len(columns) < 2:
        return contextlib.nullcontext()
    return obs.span("ingest.join_key", columns=",".join(columns))


def _count_join_key_rows(columns: tuple[str, ...], n: int) -> None:
    if len(columns) > 1:
        obs.get_registry().inc("ingest.join_key_rows", n)


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------


@dataclasses.dataclass
class EngineConfig:
    engine: str = "optimized"        # optimized | naive
    batch_size: int = 1 << 16
    load_factor: float = 0.6
    # the naive engine's nested-loop join width (None -> its widest raw
    # span); the optimized engine hashes each join's candidates exactly
    max_matches: int | None = None
    # streaming ingestion (repro.stream): block-at-a-time, out-of-core
    stream: bool = False
    block_rows: int = 1 << 14
    prefetch_blocks: int = 2
    # mapping-level planning (repro.rml.plan): projection pushdown into
    # the streamed read, FunMap-style shared-term factoring, and
    # group-by-group rule execution.  Output is byte-identical either
    # way (property-tested); False keeps the unplanned reference path.
    mapping_plan: bool = True


class Engine:
    def __init__(self, config: EngineConfig | None = None):
        self.config = config or EngineConfig()

    # -- helpers -------------------------------------------------------------

    def _term_values(
        self, dct: Dictionary, table: dict[str, np.ndarray], columns: tuple[str, ...]
    ) -> np.ndarray:
        if not columns:  # constant term: single id 0 slot (value unused)
            n = len(next(iter(table.values()))) if table else 0
            return np.zeros(n, dtype=np.int32)
        return dct.encode(join_columns([table[c] for c in columns]))

    def run(
        self,
        doc: MappingDocument,
        data_root: str = ".",
        tables: dict[str, dict[str, np.ndarray]] | None = None,
    ) -> KGResult:
        """Create the knowledge graph.  ``tables`` optionally bypasses disk:
        maps source key ('csv:child.csv') -> columnar dict."""
        t0 = time.perf_counter()
        cfg = self.config
        _touch_counters()
        mplan = None
        if cfg.mapping_plan:
            from repro.rml.plan import build_plan

            mplan = build_plan(doc)
            _plan_gauges(mplan)
        if cfg.stream:
            if cfg.engine != "optimized":
                raise ValueError(
                    "stream=True supports only the optimized engine "
                    "(the naive engine materializes everything by design)"
                )
            if cfg.block_rows < 1:
                raise ValueError(f"block_rows must be >= 1, got {cfg.block_rows}")
            return self._run_stream(doc, data_root, tables, t0, mplan=mplan)
        exec_plan = mplan.exec_plan if mplan is not None else planner.plan(doc)
        dct = Dictionary()
        cache = SourceCache(data_root)
        sources_by_key = _sources_by_key(doc)

        def get_table(source_key: str):
            if tables is not None and source_key in tables:
                return tables[source_key]
            from repro.rml.model import LogicalSource

            src = sources_by_key.get(source_key)
            if src is None:
                fmt, path, iterator = planner.parse_source_key(source_key)
                src = LogicalSource(path=path, fmt=fmt, iterator=iterator)
            with obs.span("ingest.read", source=source_key):
                return cache.get(src)

        # ---- encode the value columns each op needs (once per column set)
        value_cache: dict[tuple, np.ndarray] = {}

        def values_for(
            source_key: str, columns: tuple[str, ...], join_key: bool = False
        ) -> np.ndarray:
            key = (source_key, columns)
            if key not in value_cache:
                table = get_table(source_key)
                with obs.span("ingest.encode", source=source_key), \
                        _join_key(columns if join_key else ()):
                    value_cache[key] = self._term_values(dct, table, columns)
            return value_cache[key]

        # ---- build PJTTs once per (parent map, join columns), each with
        # host-side sorted keys whose spans size its joins
        indexes: dict[str, tuple] = {}
        span_keys: dict[str, np.ndarray] = {}
        for pkey, (psrc, pjcols, _ppat, pcols) in exec_plan.pjtt_builds.items():
            pkeys = values_for(psrc, pjcols, join_key=True)
            _count_join_key_rows(pjcols, len(pkeys))
            psubj = values_for(psrc, pcols)
            if cfg.engine == "naive":
                # raw arrays for the nested loop, which emits every raw
                # match: raw spans size it; a NULL key matches nothing
                with obs.span("ingest.pjtt_build", pjtt=pkey):
                    kd = np.where(pkeys == NULL_ID, np.iinfo(np.int32).max, pkeys)
                    indexes[pkey] = (jnp.asarray(kd), jnp.asarray(psubj))
                    span_keys[pkey] = np.sort(kd)
            else:
                indexes[pkey] = _join_index(pkey, pkeys, psubj)
                span_keys[pkey] = indexes[pkey].skeys

        # ---- per-predicate candidate estimate -> PTT capacity
        stats: dict[str, PredicateStats] = {}
        pred_candidates: dict[str, int] = {}
        op_spans: dict[int, tuple[int, int]] = {}  # op idx -> (|N_p|, max span)
        for pred, op_idxs in exec_plan.by_predicate.items():
            total = 0
            kind = exec_plan.ops[op_idxs[0]].kind
            for i in op_idxs:
                op = exec_plan.ops[i]
                n_child = len(values_for(op.source_key, op.subj_columns))
                if op.kind == "OJM":
                    # exact |N_p| and max span from the index's sorted keys:
                    # |N_p| sizes the PTT, the max span the naive join's width
                    ck = values_for(op.source_key, op.join_child_columns, join_key=True)
                    _count_join_key_rows(op.join_child_columns, len(ck))
                    with obs.span("ingest.size", op=i):
                        _start, cnt = pjtt.spans(span_keys[op.pjtt_key], ck)
                        tot, mx = int(cnt.sum()), int(cnt.max(initial=0))
                    op_spans[i] = (tot, mx)
                    total += tot
                else:
                    op_spans[i] = (n_child, 1)
                    total += n_child
            pred_candidates[pred] = total
            stats[pred] = PredicateStats(kind=kind)

        def op_input(op):
            """An op's cached columns in fixed-shape batches."""
            subj_vals = values_for(op.source_key, op.subj_columns)
            cols = {"subj": subj_vals}
            n_parent = 0
            if op.kind == "OJM":
                cols["jkey"] = values_for(op.source_key, op.join_child_columns, join_key=True)
                n_parent = len(values_for(op.parent_source_key, op.parent_join_columns))
            elif op.kind in ("SOM", "ORM"):
                cols["obj"] = values_for(op.source_key, op.obj_columns)
            else:  # CLASS: constant object
                cols["obj"] = np.zeros_like(subj_vals)
            n = len(subj_vals)
            bs = min(cfg.batch_size, pipeline.pick_batch_size(n))
            return n_parent, n, pipeline.batches(cols, bs)

        # ---- run the ops: group-by-group along the mapping plan's DAG
        # when planning is on (groups are disjoint in predicates and
        # sources, so this only reorders work), else one flat pass
        triples_out: dict[str, dict[str, list[np.ndarray]]] = {}
        if mplan is not None:
            schedule = [(g, list(g.predicates)) for g in mplan.groups]
        else:
            schedule = [(None, list(exec_plan.by_predicate))]
        for g, preds in schedule:
            span_args = {"group": g.index} if g is not None else {}
            with obs.span("plan_group", cat="plan", **span_args):
                if cfg.engine == "optimized":
                    self._run_predicates(
                        exec_plan, preds, op_input, indexes, pred_candidates,
                        stats, triples_out, dct,
                    )
                else:
                    self._run_naive(
                        exec_plan, preds, values_for, indexes, op_spans,
                        stats, triples_out, dct,
                    )

        return KGResult(
            dictionary=dct,
            triples=_assemble(exec_plan, triples_out),
            stats=stats,
            wall_time_s=time.perf_counter() - t0,
            engine=cfg.engine,
        )

    # -- the optimized engine: one predicate runner for both inputs -----------

    def _step(self, op, spat, pid, opat, hi, lo, batch, index, out, st):
        """Push one fixed-shape padded batch through the jitted step for
        ``op``; appends emitted triples to ``out`` and accumulates ``st``.
        An OJM batch hashes the exact ragged lanes of its rows' PJTT spans,
        bucketed by ``pjtt.lane_bucket``; one with no candidate makes no
        device call.  Returns ``(hi, lo, overflowed)``."""
        with obs.span("ingest.step", op=op.kind, rows=len(batch.valid)):
            # a lane whose subject, object or child key is NULL is no candidate
            other = batch.arrays["jkey" if op.kind == "OJM" else "obj"]
            live = (
                batch.valid & (batch.arrays["subj"] != NULL_ID) & (other != NULL_ID)
            )
            st.n_null += int(batch.valid.sum() - live.sum())
            sv = batch.arrays["subj"]
            if op.kind == "OJM":
                rows, pos, n_cand = pjtt.ragged_lanes(
                    index.skeys, batch.arrays["jkey"], live
                )
                if n_cand == 0:
                    return hi, lo, False
                hi, lo, is_new, psubj, ovf = _ojm_sorted_step(
                    hi, lo, index.ssubj, spat, jnp.asarray(sv), pid, opat,
                    jnp.asarray(rows), jnp.asarray(pos), np.int32(n_cand),
                )
                outputs = (is_new, psubj, ovf)
            else:
                ov = jnp.asarray(batch.arrays["obj"])
                hi, lo, is_new, ovf = _dedup_step(
                    hi, lo, spat, jnp.asarray(sv), pid, opat, ov, jnp.asarray(live)
                )
                outputs = (is_new, ovf)
            reg = obs.get_registry()
            with obs.span("ingest.sync"):
                got = [np.asarray(x) for x in outputs]  # one blocking read each
                reg.inc("ingest.host_syncs", len(got))
            with obs.span("ingest.compact"):
                if op.kind == "OJM":
                    is_new, psubj, ovf = got
                    st.n_candidates += n_cand
                    reg.inc("ingest.join_lanes", len(rows))
                    reg.inc("ingest.join_candidates", n_cand)
                    lanes = np.nonzero(is_new)[0]  # a masked lane is never new
                    rows = rows[lanes]
                    obj = psubj[lanes]
                else:
                    is_new, ovf = got
                    st.n_candidates += int(live.sum())
                    rows = np.nonzero(is_new & live)[0]
                    obj = batch.arrays["obj"][rows]
                n_emit = len(rows)
                out["subj_val"].append(sv[rows].astype(np.int32))
                out["obj_val"].append(obj.astype(np.int32))
                out["subj_pat"].append(np.full(n_emit, spat, np.int32))
                out["obj_pat"].append(np.full(n_emit, opat, np.int32))
                st.n_unique += n_emit
        return hi, lo, bool(ovf)

    def _run_predicates(
        self, exec_plan, preds, op_input, indexes, pred_candidates, stats,
        triples_out, dct: Dictionary,
    ):
        """Run ``preds`` through the jitted steps, each into a PTT sized
        from its candidate count.  ``op_input(op)`` gives an op's
        ``(n_parent, n_child, batches)``: the eager engine's cached columns
        or the streamed engine's blocks.  A PTT overflow replays the
        predicate with twice the capacity, calling ``op_input`` again."""
        cfg = self.config
        reg = obs.get_registry()
        for pred in preds:
            cap = next_pow2(int(pred_candidates[pred] / cfg.load_factor) + 16)
            while True:  # overflow -> double capacity and replay the predicate
                table = hashset.make(cap)
                hi, lo = table.hi, table.lo
                out = {k: [] for k in ("subj_pat", "subj_val", "obj_pat", "obj_val")}
                st = stats[pred]
                st.n_candidates = st.n_unique = st.n_parent = st.n_child = st.n_null = 0
                overflow = False
                for i in exec_plan.by_predicate[pred]:
                    op = exec_plan.ops[i]
                    # the dictionary's ids follow its encode order: these
                    # three scalars first, then the op's columns
                    pid = np.int32(dct.encode_scalar(op.predicate))
                    spat = np.int32(dct.encode_scalar(op.subj_pattern))
                    opat = np.int32(dct.encode_scalar(op.obj_pattern))
                    n_parent, n_child, batches = op_input(op)
                    idx = None
                    if op.kind == "OJM":
                        idx = indexes[op.pjtt_key]
                        st.n_parent += n_parent
                        st.n_child += n_child
                    for batch in batches:
                        hi, lo, overflow = self._step(
                            op, spat, pid, opat, hi, lo, batch, idx, out, st
                        )
                        if overflow:
                            break
                    if overflow:
                        break
                if not overflow:
                    triples_out[pred] = out
                    reg.inc("ingest.null_terms", st.n_null)
                    break
                reg.inc("ingest.replays")
                cap *= 2

    # -- streamed optimized engine (repro.stream) ------------------------------

    def _run_stream(self, doc, data_root, tables, t0, mplan=None) -> KGResult:
        """Out-of-core KG creation.  Every source flows block-at-a-time
        through a lazy ``read -> project -> derive -> encode -> batch``
        Dataset; only dictionary-encoded int32 ids (and the PJTT indexes
        built from them) outlive a block, so host memory is bounded by
        O(block_rows) per raw column regardless of source size.  Sized like
        the eager engine (exact span counts, streamed), with the same
        overflow-replay fallback — a replay re-reads the source rather than
        re-using a cached table.

        With a :class:`~repro.rml.plan.MappingPlan` (``mapping_plan=True``)
        three planner-driven optimizations engage, none of which changes
        the produced KG: projections are pushed into the readers (pruned
        columns never materialize), shared term columns are evaluated once
        per source scan and served from an int32 cache, and the rule
        groups run as a DAG — each group's factored cache and PJTT indexes
        live only for that group."""
        import os

        from repro.stream import Dataset, read_source
        from repro.stream.block import Block
        from repro.stream.datasource import is_sharded_path

        cfg = self.config
        exec_plan = mplan.exec_plan if mplan is not None else planner.plan(doc)
        reg = obs.get_registry()
        _touch_counters()
        dct = Dictionary()
        block_rows = cfg.block_rows
        # block_rows bounds I/O granularity; batch_size still bounds the
        # jitted device batch (a block is split into padded batches if the
        # user asked for a smaller device shape)
        device_rows = min(cfg.batch_size, block_rows)
        prefetch = cfg.prefetch_blocks
        sources_by_key = _sources_by_key(doc)

        def resolve(source_key: str) -> tuple[str, str, str | None]:
            """source_key -> (fmt, absolute path, iterator)."""
            src = sources_by_key.get(source_key)
            if src is not None:
                fmt, path, iterator = src.fmt, src.path, src.iterator
            else:
                fmt, path, iterator = planner.parse_source_key(source_key)
            if not os.path.isabs(path):
                path = os.path.join(data_root, path)
            return fmt, path, iterator

        def dataset_for(source_key: str) -> Dataset:
            if tables is not None and source_key in tables:
                return Dataset.from_table(tables[source_key], block_rows=block_rows)
            fmt, path, iterator = resolve(source_key)
            return read_source(
                path, fmt=fmt, block_rows=block_rows, iterator=iterator
            )

        def fill_of(source_key: str) -> str | None:
            """Projection fill policy: "" (union-fill) for genuinely
            heterogeneous sources — JSON records and glob-sharded files —
            matching the eager loader's key-union; None (strict KeyError,
            matching the eager engine's table[c]) for fixed-schema
            single-file CSV/TSV and the tables bypass, where a missing
            column is a mapping typo."""
            if tables is not None and source_key in tables:
                return None
            fmt, path, _ = resolve(source_key)
            if fmt == "json":
                return ""
            return "" if is_sharded_path(path) else None

        def derived(block: Block, columns: tuple) -> np.ndarray:
            """String value column for a (possibly multi-column) term; a
            constant term is int32 zeros, which Encode passes through."""
            if not columns:
                return np.zeros(block.n_rows, dtype=np.int32)
            return join_columns([block.columns[c] for c in columns])

        def key_column(block: Block, columns: tuple) -> np.ndarray:
            """A join key for a pipeline: one column as read (the
            pipeline's Encode gives it ids), or two or more joined and
            encoded here, under ``ingest.join_key``."""
            if len(columns) == 1:
                return block.columns[columns[0]]
            with _join_key(columns):
                return dct.encode(derived(block, columns))

        def key_prefetch(columns: tuple) -> int:
            """A pipeline that builds a key from two or more columns runs
            on the engine's thread, so that its ``ingest.join_key`` spans
            nest in the engine's open span."""
            return prefetch if len(columns) < 2 else 0

        def op_dataset(op) -> Dataset:
            if op.kind == "OJM":
                extra: tuple = op.join_child_columns
            elif op.kind in ("SOM", "ORM"):
                extra = tuple(op.obj_columns)
            else:
                extra = ()
            needed = tuple(dict.fromkeys(tuple(op.subj_columns) + extra))

            def to_term_columns(block: Block) -> Block:
                cols = {"subj": derived(block, op.subj_columns)}
                if op.kind == "OJM":
                    cols["jkey"] = key_column(block, op.join_child_columns)
                elif op.kind in ("SOM", "ORM"):
                    cols["obj"] = derived(block, op.obj_columns)
                else:  # CLASS: constant object
                    cols["obj"] = np.zeros(block.n_rows, dtype=np.int32)
                return Block(cols)

            # all-constant ops read no columns; skip the projection entirely
            # (a zero-column block would lose its row count) and let
            # to_term_columns derive zeros from the raw block's n_rows
            ds = dataset_for(op.source_key)
            if needed:
                ds = ds.project(*needed, fill=fill_of(op.source_key))
            return ds.map_blocks(to_term_columns).encode(dct).batch(block_rows)

        # ---- referenced-column validation for union-fill sources: a column
        # absent from EVERY record is a mapping typo (the eager engine's
        # table[c] raises on it); fill-mode projection would otherwise
        # silently emit ""-term triples.  The scan also yields row counts,
        # sparing these sources the sizing count pass below.
        refcols: dict[str, set] = {}
        for op in exec_plan.ops:
            cols = refcols.setdefault(op.source_key, set())
            cols.update(op.subj_columns)
            if op.kind == "OJM":
                cols.update(op.join_child_columns)
            else:
                cols.update(op.obj_columns)
        for psrc_, pjcols_, _ppat_, pcols_ in exec_plan.pjtt_builds.values():
            cols = refcols.setdefault(psrc_, set())
            cols.update(pjcols_)
            cols.update(pcols_)
        row_counts: dict[str, int] = {}
        for skey, cols in refcols.items():
            if not cols or fill_of(skey) is None:
                continue
            seen: set = set()
            n = 0
            for block in _spanned("ingest.read", dataset_for(skey).iter_blocks(prefetch)):
                seen |= set(block.schema)
                n += block.n_rows
            row_counts[skey] = n
            missing = cols - seen
            if missing:
                raise KeyError(
                    f"columns {sorted(missing)} not present in any record of "
                    f"source {skey!r}"
                )

        # ---- planner-on state: the factored shared-term cache.  Keyed
        # (source_key, columns) like the eager path's value cache, holding
        # the dictionary-encoded int32 value column of a term evaluated by
        # >= 2 sites.  Filled per group, freed when the group completes.
        value_cache: dict[tuple, np.ndarray] = {}
        # the (source, columns) that some join keys on: two or more columns
        # factored for it are built under ingest.join_key
        join_keys = {(op.source_key, op.join_child_columns)
                     for op in exec_plan.ops if op.kind == "OJM"}
        join_keys.update((psrc_, pjcols_)
                         for psrc_, pjcols_, _p, _c in exec_plan.pjtt_builds.values())

        def build_factored(group) -> None:
            """One streaming pass per source with shared terms: evaluate
            and encode every factored term column of the group (FunMap's
            pre-materialization, scoped to the group's lifetime)."""
            per_src: dict[str, list[tuple]] = {}
            for (skey, colset), _sh in mplan.shared.items():
                if skey in group.sources:
                    per_src.setdefault(skey, []).append(colset)
            for skey, colsets in sorted(per_src.items()):
                union_raw = tuple(
                    dict.fromkeys(c for cols in colsets for c in cols)
                )
                ds = dataset_for(skey).project(
                    *union_raw, fill=fill_of(skey), pushdown=True
                )
                chunks: dict[tuple, list] = {cols: [] for cols in colsets}
                n = 0
                with obs.span("ingest.encode", source=skey):
                    for block in _spanned("ingest.read", ds.iter_blocks(prefetch)):
                        for cols in colsets:
                            with _join_key(cols if (skey, cols) in join_keys else ()):
                                chunks[cols].append(dct.encode(derived(block, cols)))
                        n += block.n_rows
                    for cols in colsets:
                        value_cache[(skey, cols)] = (
                            np.concatenate(chunks[cols])
                            if chunks[cols]
                            else np.zeros(0, np.int32)
                        )
                row_counts[skey] = n

        def count_rows(source_key: str) -> int:
            n = row_counts.get(source_key)
            if n is None:
                with obs.span("ingest.read", source=source_key):
                    n = row_counts[source_key] = dataset_for(source_key).count()
            return n

        def op_blocks(op):
            """Planner-on block stream for one op: slots whose term column
            is in the factored cache are sliced from it; remaining slots
            stream the source with the projection pushed into the read."""
            slots: list[tuple[str, tuple]] = [("subj", tuple(op.subj_columns))]
            if op.kind == "OJM":
                slots.append(("jkey", op.join_child_columns))
            elif op.kind in ("SOM", "ORM"):
                slots.append(("obj", tuple(op.obj_columns)))
            else:  # CLASS: constant object
                slots.append(("obj", ()))
            cached: dict[str, np.ndarray] = {}
            uncached: list[tuple[str, tuple]] = []
            for name, cols in slots:
                if not cols:
                    continue  # constant slot: zeros derived per block
                arr = value_cache.get((op.source_key, cols))
                if arr is not None:
                    cached[name] = arr
                else:
                    uncached.append((name, cols))
            if not uncached:
                # fully factored (or all-constant): no re-read at all
                if cached:
                    length = len(next(iter(cached.values())))
                else:
                    length = count_rows(op.source_key)
                for start in range(0, length, block_rows):
                    end = min(start + block_rows, length)
                    cols_out = {}
                    for name, _cols in slots:
                        if name in cached:
                            cols_out[name] = cached[name][start:end]
                        else:
                            cols_out[name] = np.zeros(end - start, np.int32)
                    reg.inc("plan.factored_rows", (end - start) * len(cached))
                    yield Block(cols_out)
                return
            needed = tuple(
                dict.fromkeys(c for _n, cols in uncached for c in cols)
            )
            ds = dataset_for(op.source_key).project(
                *needed, fill=fill_of(op.source_key), pushdown=True
            )
            offset = 0
            for block in _spanned("ingest.read", ds.iter_blocks(prefetch)):
                m = block.n_rows
                cols_out = {}
                with obs.span("ingest.encode", source=op.source_key):
                    for name, cols in slots:
                        if name in cached:
                            cols_out[name] = cached[name][offset:offset + m]
                        elif not cols:
                            cols_out[name] = np.zeros(m, np.int32)
                        else:
                            with _join_key(cols if name == "jkey" else ()):
                                cols_out[name] = dct.encode(derived(block, cols))
                if cached:
                    reg.inc("plan.factored_rows", m * len(cached))
                offset += m
                yield Block(cols_out)

        # ---- PJTT builds: stream the parent once; retain only int32 ids
        indexes: dict[str, _JoinIndex] = {}
        parent_counts: dict[str, int] = {}

        def parent_columns(psrc, pjcols, pcols):
            """The encoded join-key and subject columns of a parent."""
            kc = value_cache.get((psrc, pjcols))
            sc = value_cache.get((psrc, tuple(pcols)))
            if kc is not None and (sc is not None or not pcols):
                # both columns already factored: build from the cache
                reg.inc("plan.factored_rows", 2 * len(kc))
                return kc, sc if sc is not None else np.zeros(len(kc), np.int32)
            needed = tuple(dict.fromkeys(pjcols + tuple(pcols)))

            def to_index_columns(block: Block) -> Block:
                return Block(
                    {"key": key_column(block, pjcols), "subj": derived(block, pcols)}
                )

            ds = dataset_for(psrc).project(
                *needed, fill=fill_of(psrc), pushdown=mplan is not None,
            )
            ds = ds.map_blocks(to_index_columns).encode(dct)
            kchunks, schunks = [], []
            for block in ds.iter_blocks(key_prefetch(pjcols)):
                kchunks.append(block.columns["key"])
                schunks.append(block.columns["subj"])
            if not kchunks:
                return np.zeros(0, np.int32), np.zeros(0, np.int32)
            return np.concatenate(kchunks), np.concatenate(schunks)

        def build_pjtts(pjtt_items) -> None:
            for pkey, (psrc, pjcols, _ppat, pcols) in pjtt_items:
                # the scan's blocks come out encoded: its reads count here
                with obs.span("ingest.encode", source=psrc):
                    pkeys, psubj = parent_columns(psrc, pjcols, pcols)
                _count_join_key_rows(pjcols, len(pkeys))
                indexes[pkey] = _join_index(pkey, pkeys, psubj)
                parent_counts[pkey] = len(pkeys)
                row_counts[psrc] = len(pkeys)

        # ---- sizing pre-pass: exact |N_p|, streamed
        stats: dict[str, PredicateStats] = {}
        pred_candidates: dict[str, int] = {}

        def size_predicates(pred_list) -> None:
            for pred in pred_list:
                with obs.span("ingest.size", pred=pred):
                    size_predicate(pred)

        def size_predicate(pred) -> None:
            op_idxs = exec_plan.by_predicate[pred]
            total = 0
            stats[pred] = PredicateStats(kind=exec_plan.ops[op_idxs[0]].kind)
            for i in op_idxs:
                op = exec_plan.ops[i]
                if op.kind == "OJM":
                    spk = indexes[op.pjtt_key].skeys
                    jcols = op.join_child_columns
                    ck_all = value_cache.get((op.source_key, jcols))
                    if ck_all is not None:
                        # factored child key: span counts with no re-read
                        tot = int(pjtt.spans(spk, ck_all)[1].sum())
                        row_counts[op.source_key] = len(ck_all)
                    else:
                        tot = n = 0
                        ds = (
                            dataset_for(op.source_key)
                            .project(
                                *jcols,
                                fill=fill_of(op.source_key),
                                pushdown=mplan is not None,
                            )
                            .map_blocks(lambda b: Block({"key": key_column(b, jcols)}))
                            .encode(dct)
                        )
                        blocks = ds.iter_blocks(key_prefetch(jcols))
                        for block in _spanned("ingest.encode", blocks):
                            ck = block.columns["key"]
                            tot += int(pjtt.spans(spk, ck)[1].sum())
                            n += block.n_rows
                        row_counts[op.source_key] = n
                    _count_join_key_rows(jcols, row_counts[op.source_key])
                    total += tot
                else:
                    total += count_rows(op.source_key)
            pred_candidates[pred] = total

        # ---- run the ops, block-at-a-time
        def op_input(op):
            """An op's blocks, each split into device batches."""
            n_parent = n_child = 0
            if op.kind == "OJM":
                n_parent = parent_counts[op.pjtt_key]
                n_child = row_counts[op.source_key]
            blocks = (
                op_blocks(op)
                if mplan is not None
                # the unplanned stream encodes as it reads
                else _spanned("ingest.encode", op_dataset(op).iter_blocks(
                    key_prefetch(op.join_child_columns)))
            )
            batches = (
                batch
                for block in blocks
                for batch in pipeline.batches(block.columns, device_rows)
            )
            return n_parent, n_child, batches

        triples_out: dict[str, dict[str, list[np.ndarray]]] = {}

        def run_predicates(preds) -> None:
            self._run_predicates(
                exec_plan, preds, op_input, indexes, pred_candidates, stats,
                triples_out, dct,
            )

        if mplan is None:
            build_pjtts(exec_plan.pjtt_builds.items())
            size_predicates(list(exec_plan.by_predicate))
            run_predicates(list(exec_plan.by_predicate))
        else:
            # group-by-group along the DAG: factored cache and PJTT
            # indexes are built at group entry and freed at group exit
            for g in mplan.groups:
                with obs.span("plan_group", cat="plan", group=g.index,
                              rules=len(g.op_indices)):
                    build_factored(g)
                    build_pjtts(
                        (pk, exec_plan.pjtt_builds[pk]) for pk in g.pjtt_keys
                    )
                    size_predicates(list(g.predicates))
                    run_predicates(list(g.predicates))
                for skey in g.sources:
                    for key in [k for k in value_cache if k[0] == skey]:
                        del value_cache[key]
                for pk in g.pjtt_keys:
                    indexes.pop(pk, None)

        stats = {pred: stats[pred] for pred in exec_plan.by_predicate}
        return KGResult(
            dictionary=dct,
            triples=_assemble(exec_plan, triples_out),
            stats=stats,
            wall_time_s=time.perf_counter() - t0,
            engine="stream",
        )

    # -- naive engine ----------------------------------------------------------

    def _run_naive(
        self, exec_plan, preds, values_for, indexes, op_spans, stats,
        triples_out, dct,
    ):
        cfg = self.config
        for pred in preds:
            op_idxs = exec_plan.by_predicate[pred]
            khis, klos, valids = [], [], []
            svs, ovs, spats, opats = [], [], [], []
            st = stats[pred]
            for i in op_idxs:
                op = exec_plan.ops[i]
                pid = np.int32(dct.encode_scalar(op.predicate))
                spat = np.int32(dct.encode_scalar(op.subj_pattern))
                opat = np.int32(dct.encode_scalar(op.obj_pattern))
                subj_vals = values_for(op.source_key, op.subj_columns)
                n = len(subj_vals)
                if op.kind == "OJM":
                    pkeys, psubj = indexes[op.pjtt_key]
                    tot, mx = op_spans[i]
                    K = cfg.max_matches or max(int(mx), 1)
                    ck = jnp.asarray(values_for(op.source_key, op.join_child_columns))
                    jr = _naive_join_step(pkeys, psubj, K, ck)
                    if bool(jr.truncated):
                        raise RuntimeError("naive join exceeded max_matches")
                    live = (subj_vals != NULL_ID) & (np.asarray(ck) != NULL_ID)
                    subj = np.broadcast_to(subj_vals[:, None], (n, K)).reshape(-1)
                    obj = np.asarray(jr.subjects).reshape(-1)
                    v = (np.asarray(jr.valid) & live[:, None]).reshape(-1)
                    khi, klo = _naive_keys_step(
                        spat, jnp.asarray(subj), pid, opat, jnp.asarray(obj)
                    )
                    st.n_parent += pkeys.shape[0]
                    st.n_child += n
                else:
                    if op.kind == "CLASS":
                        obj = np.zeros_like(subj_vals)
                    else:
                        obj = values_for(op.source_key, op.obj_columns)
                    live = (subj_vals != NULL_ID) & (obj != NULL_ID)
                    subj, v = subj_vals, live
                    khi, klo = _naive_keys_step(
                        spat, jnp.asarray(subj), pid, opat, jnp.asarray(obj)
                    )
                st.n_null += int(n - live.sum())
                khis.append(np.asarray(khi))
                klos.append(np.asarray(klo))
                valids.append(v)
                svs.append(np.asarray(subj, dtype=np.int32))
                ovs.append(np.asarray(obj, dtype=np.int32))
                spats.append(np.full(len(v), spat, np.int32))
                opats.append(np.full(len(v), opat, np.int32))
            khi = np.concatenate(khis)
            klo = np.concatenate(klos)
            v = np.concatenate(valids)
            st.n_candidates = int(v.sum())
            obs.get_registry().inc("ingest.null_terms", st.n_null)
            dd = _naive_dedup(jnp.asarray(khi), jnp.asarray(klo), jnp.asarray(v))
            mask = np.asarray(dd.uniq_mask)
            st.n_unique = int(mask.sum())
            triples_out[pred] = {
                "subj_pat": [np.concatenate(spats)[mask]],
                "subj_val": [np.concatenate(svs)[mask]],
                "obj_pat": [np.concatenate(opats)[mask]],
                "obj_val": [np.concatenate(ovs)[mask]],
            }


def create_kg(
    doc: MappingDocument,
    data_root: str = ".",
    tables=None,
    **config,
) -> KGResult:
    """One-call public API: parse-level document -> knowledge graph."""
    return Engine(EngineConfig(**config)).run(doc, data_root=data_root, tables=tables)
