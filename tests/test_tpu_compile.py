"""The main path's device programs compile for a TPU v5e.

Nothing runs: each program is lowered for one chip of a described
``v5e:2x2`` topology and compiled by the TPU compiler at the sizes the
chip smoke test uses, so a program the chip would refuse fails here.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import executor as E
from repro.kg import store as S
from repro.kg.store import TripleStore
from repro.serve import chain as K
from repro.serve import fastpath as FP
from repro.serve import parse_select
from repro.serve.exec import get_executor

BATCH = 1 << 16            # the engine's default step batch
PTT_SLOTS = 1 << 22        # the PTT of a ~2.5M-candidate predicate
PJTT_KEYS = 1 << 20        # the PJTT of a 1M-row parent source
SORT_ROWS = 4 << 20        # a 4M-triple store's index sort
STORE_N = 1_340_000        # the chip smoke test's OJM KG
STORE_TERMS = 790_000


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dedup_step_compiles(one_chip):
    u32 = spec((PTT_SLOTS,), jnp.uint32, one_chip)
    i32 = spec((), jnp.int32, one_chip)
    col = spec((BATCH,), jnp.int32, one_chip)
    valid = spec((BATCH,), jnp.bool_, one_chip)
    E._dedup_step.lower(u32, u32, i32, col, i32, i32, col, valid).compile()


def test_ojm_sorted_step_compiles(one_chip):
    u32 = spec((PTT_SLOTS,), jnp.uint32, one_chip)
    keys = spec((PJTT_KEYS,), jnp.int32, one_chip)
    i32 = spec((), jnp.int32, one_chip)
    col = spec((BATCH,), jnp.int32, one_chip)
    valid = spec((BATCH,), jnp.bool_, one_chip)
    E._ojm_sorted_step.lower(
        u32, u32, keys, keys, i32, col, i32, i32, 8, col, valid
    ).compile()


def test_lexsort3_compiles(one_chip):
    col = spec((SORT_ROWS,), jnp.int32, one_chip)
    S._lexsort3.lower(col, col, col).compile()


def _chain_spec(n_readers: int) -> K.ChainSpec:
    """A planner-built chain of ``n_readers`` pattern readers, resized to
    the smoke test's store: the same index orders and sources, the
    bisection depths of a ``STORE_N``-row index."""
    # a rare anchor predicate and common join predicates, so the planner
    # scans the anchor and bind-joins the rest
    triples = [(f"<http://ex/s{i}>", "<http://ex/p1>", f'"v{i % 7}"')
               for i in range(40)]
    triples += [(f"<http://ex/s{i}>", "<http://ex/p0>", '"anchor"')
                for i in range(5)]
    store = TripleStore.from_ntriples(sorted(set(triples)))
    ex = get_executor(store)
    texts = {
        1: 'SELECT * WHERE { ?s <http://ex/p0> "anchor" }',
        3: 'SELECT * WHERE { ?s <http://ex/p0> "anchor" . '
           '?s <http://ex/p1> ?b . ?s <http://ex/p1> ?c }',
    }
    fp = FP.build(ex, ex.plan(parse_select(texts[n_readers])))
    assert fp is not None and len(fp.spec.readers) == n_readers
    rounds = STORE_N.bit_length()
    return dataclasses.replace(
        fp.spec,
        readers=tuple(
            dataclasses.replace(r, prim_rounds=rounds) for r in fp.spec.readers
        ),
        rounds=rounds,
        store_n=STORE_N,
    )


@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("n_readers", [1, 3])
def test_fastpath_chain_compiles(one_chip, n_readers, batch):
    chain = _chain_spec(n_readers)
    caps = (64,) * n_readers
    col = spec((STORE_N,), jnp.int32, one_chip)
    starts = spec((STORE_TERMS + 1,), jnp.int32, one_chip)
    operands = [col] * 5 + [starts]
    qbuf = spec((batch, K.qrow_width(n_readers)), jnp.int32, one_chip)
    fn = jax.jit(K.make_batched(chain, caps))
    fn.lower(*operands * n_readers, qbuf).compile()
