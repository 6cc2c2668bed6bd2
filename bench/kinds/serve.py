"""Open-loop query serving over the KG of a testbed.

Set-up makes the configuration's testbed from the seed, joins it with
the benchmark's own vectorised reference (``reference.ojm_kg``) and
builds the program's ``TripleStore`` straight from those columns: term
ids are the ranks of the rendered terms, as ``rdfize --emit kgz`` gives
them.  It starts a ``KGServer`` (``warmup=False``) in this process,
draws the window's schedule from the seed, runs every signature of the
schedule through the server's executor (all of the schedule's anchors,
so every capacity grows to what the window needs, then once at every
batch pad up to ``max_pad``), and starts the load generator, a child
process that connects its clients and waits.

The schedule has ``rate`` × ``--seconds`` requests: inter-arrival gaps
that are the quantiles of an exponential at the rate, the mix's shapes
in equal shares, and anchor ranks that are the quantiles of a Zipf law
over the anchor population, each list shuffled by the seed, and the
population in an order drawn from the seed.  Every seed thus offers the
same gaps, shapes and ranks, in another order.

``query_p50_ms`` and ``query_p95_ms`` are taken over every answered
request, from the time it was due to its decoded answer.  The check
evaluates each query text with the plain reference (``sparql.py``) over
the reference KG and compares every answer the clients received: the
variables, the rows in order, and the solution count.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import string
import subprocess
import sys
import time

import numpy as np

import harness
import reference
import sparql
import testbed

RANK_SALT, GAP_SALT, SHAPE_SALT, ORDER_SALT = 1, 2, 3, 4


@dataclasses.dataclass
class Schedule:
    texts: list          # distinct query texts
    text_of: np.ndarray  # request -> index into texts
    shape_of: np.ndarray
    due: np.ndarray      # seconds after the window opens


@dataclasses.dataclass
class State:
    kg: reference.KG
    tb: testbed.Testbed
    traffic: dict
    schedule: Schedule
    workdir: str
    server: object = None
    child: object = None
    results: dict = dataclasses.field(default_factory=dict)


def populations(tb: testbed.Testbed) -> "dict[str, np.ndarray]":
    """Anchor populations: the row identities of the mutations and of
    the exons that take part in the join."""
    jc, jp = reference.join_pairs(tb)
    return {"mutation": np.unique(jc), "exon": np.unique(jp)}


def anchor_terms(kind: str, row: int) -> "dict[str, str]":
    base = testbed.BASE
    if kind == "mutation":
        return {"mutation": f"<{base}mutation/MUTATION_ID_{row}>"}
    return {f"exon{i}": f"<{base}exon{i}/EXON_ID_{row}>" for i in (1, 2)}


def zipf_ranks(n: int, population: int, s: float) -> np.ndarray:
    """The ``n`` quantiles, at (j + 1/2)/n, of a Zipf law of exponent
    ``s`` over ranks 0..population-1."""
    cdf = np.cumsum(1.0 / np.arange(1, population + 1) ** s)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, (np.arange(n) + 0.5) / n), population - 1)


def make_schedule(tb: testbed.Testbed, traffic: dict, seed: int, seconds: float,
                  rate: float) -> Schedule:
    shapes = traffic["shapes"]
    n = max(1, int(round(rate * seconds)))
    pops = populations(tb)
    order = {k: np.random.default_rng([seed, ORDER_SALT, i]).permutation(v)
             for i, (k, v) in enumerate(sorted(pops.items()))}
    shape_of = np.arange(n) % len(shapes)
    np.random.default_rng([seed, SHAPE_SALT]).shuffle(shape_of)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    np.random.default_rng([seed, GAP_SALT]).shuffle(gaps)
    due = np.cumsum(gaps)
    texts, index, text_of = [], {}, np.zeros(n, np.int64)
    for k, shape in enumerate(shapes):
        at = np.flatnonzero(shape_of == k)
        pop = order[shape["anchor"]]
        ranks = zipf_ranks(len(at), len(pop), traffic["zipf_s"])
        np.random.default_rng([seed, RANK_SALT, k]).shuffle(ranks)
        tmpl = string.Template(shape["query"])
        for i, r in zip(at.tolist(), ranks.tolist()):
            text = tmpl.substitute(anchor_terms(shape["anchor"], int(pop[r])))
            text_of[i] = index.setdefault(text, len(texts))
            if text_of[i] == len(texts):
                texts.append(text)
    return Schedule(texts, text_of, shape_of, due)


def build_store(kg: reference.KG):
    """The program's store over the reference KG's columns: one
    dictionary string per rendered IRI, term id = rank."""
    from repro.data.encoder import Dictionary
    from repro.kg.store import TripleStore

    if not np.char.startswith(kg.terms, "<").all():
        raise harness.BenchError("the serve store is built for IRIs only")
    strings = ["iri:" + t[1:-1] for t in kg.terms.tolist()]
    ids = np.arange(len(strings), dtype=np.int32)
    return TripleStore.build(Dictionary.from_strings(strings), ids, ids, kg.s, kg.p, kg.o)


def warm(server, schedule: Schedule, shapes: list, max_pad: int) -> None:
    """Compile what the window runs: each signature over all of its
    anchors in the schedule (capacities grow to fit them), then once at
    each batch pad, through the server's own executor."""
    from repro.serve import algebra

    ex = server.executor
    for k in range(len(shapes)):
        idx = np.unique(schedule.text_of[schedule.shape_of == k])
        qs = [algebra.parse_select(schedule.texts[i]) for i in idx.tolist()]
        if not qs:
            continue
        plan = ex.plan(qs[0])
        for a in range(0, len(qs), max_pad):
            ex.execute(plan, qs[a:a + max_pad])
        pad = 1
        while pad <= max_pad:
            ex.execute(plan, (qs * pad)[:pad])
            pad *= 2


def setup(ctx, rate: "float | None" = None) -> State:
    from repro.serve.server import KGServer

    spec = ctx.cell.config["testbed"]
    traffic = ctx.cell.traffic
    tb = testbed.make(spec["kind"], spec["rows"], spec["dup_rate"], spec["n_poms"], ctx.seed)
    kg = reference.ojm_kg(tb)
    store = build_store(kg)
    server = KGServer(store, port=0, log=False, warmup=False).start()
    schedule = make_schedule(tb, traffic, ctx.seed, ctx.seconds,
                             rate if rate is not None else traffic["rate_qps"])
    state = State(kg, tb, traffic, schedule, ctx.workdir, server=server)
    try:
        warm(server, schedule, traffic["shapes"], traffic["max_pad"])
        start_loadgen(state)
    except BaseException:
        release(state)
        raise
    return state


def start_loadgen(state: State) -> None:
    sched = state.schedule
    path = os.path.join(state.workdir, "schedule.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump({
            "address": f"127.0.0.1:{state.server.port}",
            "clients": state.traffic["clients"],
            "drain_s": state.traffic["drain_s"],
            "texts": sched.texts,
            "text_of": sched.text_of.tolist(),
            "due": sched.due.tolist(),
        }, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    state.child = subprocess.Popen(
        [sys.executable, os.path.join(harness.BENCH, "loadgen.py"), path,
         os.path.join(state.workdir, "results.json")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
    )
    if state.child.stdout.readline().strip() != "ready":
        raise harness.BenchError("the load generator did not start")


def window(state: State, seconds: float) -> dict:
    child = state.child
    t0 = time.monotonic() + 0.05
    child.stdin.write(f"go {t0!r}\n")
    child.stdin.flush()
    if child.stdout.readline().strip() != "done":
        raise harness.BenchError("the load generator ended without its results")
    child.wait(timeout=60)
    with open(os.path.join(state.workdir, "results.json"), encoding="utf-8") as f:
        res = json.load(f)
    state.results = res
    due = state.schedule.due.tolist()
    lat = [1e3 * (d - due[i]) for i, d in enumerate(res["done"]) if d is not None]
    late = [1e3 * (s - due[i]) for i, s in enumerate(res["sent"]) if s is not None]
    n = len(due)
    out = {
        "attempted": n,
        "failed": n - len(lat),
        "answered": len(lat),
        "late_ms": late,
        "end_to_end": {},
    }
    if lat:
        out["end_to_end"] = {"query_p50_ms": harness.quantile(lat, 50),
                             "query_p95_ms": harness.quantile(lat, 95)}
    return out


@contextlib.contextmanager
def annotate(state: State):
    """The server's own spans come with a later tracing change; the
    window's span is all the host labels there are."""
    yield


def stop_loadgen(state: State) -> None:
    child, state.child = state.child, None
    if child is None:
        return
    if child.poll() is None:
        child.kill()
    child.wait(timeout=30)
    for stream in (child.stdin, child.stdout):
        with contextlib.suppress(OSError):
            stream.close()


def release(state: State) -> None:
    stop_loadgen(state)
    if state.server is not None:
        state.server.stop()
        state.server = None


def _answers(state: State, cap: "int | None" = None) -> "dict[int, tuple]":
    graph = sparql.Graph(state.kg, cap=cap)
    out = {}
    for key in state.results.get("answers", {}):
        q = sparql.parse(state.schedule.texts[int(key)])
        out[int(key)] = sparql.evaluate(graph, q)
    return out


def _wrong(state: State, want: dict) -> int:
    wrong = 0
    for key, per in state.results.get("answers", {}).items():
        out_vars, rows = want[int(key)]
        expect = [list(out_vars), [list(r) for r in rows], len(rows)]
        for answer, count in per.items():
            if json.loads(answer) != expect:
                wrong += count
    return wrong


def check(state: State, win: dict) -> "list[tuple[str, float, float]]":
    return [
        ("wrong_answers", _wrong(state, _answers(state)), 0),
        ("failed_requests", win["failed"], 0),
    ]


def control(state: State) -> "list[tuple[str, float, float]]":
    """The check's readings with the control in the program's place: the
    reference with its guarantee of complete answers broken, every
    pattern scan held to the one row of the smallest capacity the
    executor starts a scan at, with no re-run when more rows match."""
    want = _answers(state)
    got = _answers(state, cap=1)
    wrong = 0
    for key, per in state.results.get("answers", {}).items():
        if got[int(key)] != want[int(key)]:
            wrong += sum(per.values())
    return [("wrong_answers", wrong, 0), ("failed_requests", 0, 0)]
