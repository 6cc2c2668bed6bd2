#!/usr/bin/env bash
# End-to-end sharding smoke: generate a testbed, rdfize it into a
# 2-shard KG (multi-process shard builds), then assert three access
# paths against the unsharded snapshot built from the same sources:
#
#   1. repro.api.connect(<manifest>)  — in-process scatter/gather session,
#      byte-identical answers (plain / chain / GROUP BY-COUNT / DISTINCT),
#      insert routed to exactly one shard;
#   2. launch.serve --kg <manifest>   — the coordinator NDJSON server
#      (port 0, parsed from the startup log), queried over the wire with
#      the ordinary client, fan-out counters checked via the metrics op;
#   3. launch.query --kg <manifest>   — the CLI front door.
#
#   scripts/shard_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
WORK="$(mktemp -d)"
trap 'kill "${SERVER_PID:-}" 2>/dev/null || true; rm -rf "$WORK"' EXIT

wait_for_port() {
    local log="$1" port=""
    for _ in $(seq 150); do
        port="$(sed -n 's/.*\[serve\] listening on [^ :]*:\([0-9][0-9]*\).*/\1/p' "$log" | head -n 1)"
        if [ -n "$port" ]; then echo "$port"; return 0; fi
        sleep 0.2
    done
    echo "coordinator never announced a listening port; log follows:" >&2
    cat "$log" >&2
    return 1
}

python - "$WORK" <<'EOF'
import sys
from repro.rml import generator, serializer
tb = generator.make_testbed("SOM", 200, 0.5, n_poms=2, seed=3)
tb.write(sys.argv[1])
serializer.write_turtle(tb.doc, sys.argv[1] + "/mapping.ttl")
EOF

# the same sources, unsharded and sharded (2 shards)
python -m repro.launch.rdfize \
    --mapping "$WORK/mapping.ttl" --data-root "$WORK" \
    --out "$WORK/kg.kgz" --emit kgz
python -m repro.launch.rdfize \
    --mapping "$WORK/mapping.ttl" --data-root "$WORK" \
    --out "$WORK/kg.shards.json" --emit kgz --shards 2

# 1) in-process shard session: byte-identical to the single store,
#    routed insert touches exactly one shard
python - "$WORK" <<'EOF'
import sys
from repro import api

work = sys.argv[1]
GN = "<http://repro.org/vocab/gene_name>"
AN = "<http://repro.org/vocab/accession_number>"
QUERIES = [
    f"SELECT * WHERE {{ ?m {GN} ?g }}",
    f"SELECT * WHERE {{ ?m {GN} ?g . ?m {AN} ?a }} LIMIT 10",
    f"SELECT ?g (COUNT(?m) AS ?n) WHERE {{ ?m {GN} ?g }} "
    "GROUP BY ?g ORDER BY DESC(?n)",
    f"SELECT DISTINCT ?g WHERE {{ ?m {GN} ?g }} ORDER BY ?g LIMIT 5",
]
with api.connect(f"{work}/kg.kgz") as single, \
        api.connect(f"{work}/kg.shards.json") as sharded:
    for q in QUERIES:
        a, b = single.query(q), sharded.query(q)
        assert a.rows == b.rows, (q, a.rows[:3], b.rows[:3])
        assert a.n_total == b.n_total, (q, a.n_total, b.n_total)
    r = sharded.insert([["<http://smoke/shard1>", GN, '"sharded-live"']])
    assert r["inserted"] == 1 and r["shards_touched"] == 1, r
    got = sharded.query(f"SELECT ?g WHERE {{ <http://smoke/shard1> {GN} ?g }}")
    assert got.rows == [('"sharded-live"',)], got.rows
print(f"shard session smoke OK: {len(QUERIES)} queries byte-identical, "
      "insert routed to 1 shard")
EOF

# 2) the coordinator server over the wire
python -m repro.launch.serve --kg "$WORK/kg.shards.json" --port 0 \
    2>"$WORK/coord.log" &
SERVER_PID=$!
PORT="$(wait_for_port "$WORK/coord.log")"
echo "[smoke] coordinator is up on port $PORT"

python - "$PORT" <<'EOF'
import sys
from repro import api

GN = "<http://repro.org/vocab/gene_name>"
with api.connect(f"127.0.0.1:{int(sys.argv[1])}", retry_s=30) as c:
    scattered = c.query(f"SELECT * WHERE {{ ?m {GN} ?g }}")
    assert scattered.n_total > 0 and scattered.rows, scattered
    m0, _g0 = scattered.rows[0]
    routed = c.query(f"SELECT ?g WHERE {{ {m0} {GN} ?g }}")
    assert routed.n_total >= 1, routed
    r = c.insert([["<http://smoke/wire1>", GN, '"wire-live"']])
    assert r["inserted"] == 1 and r["shards_touched"] == 1, r
    got = c.query(f"SELECT ?g WHERE {{ <http://smoke/wire1> {GN} ?g }}")
    assert got.rows == [('"wire-live"',)], got.rows
    met = c.metrics()["metrics"]
    cnt = met["counters"]
    # the scatter fanned out to both shards; the routed queries hit one
    assert cnt.get("shard.scattered", 0) >= 1, cnt
    assert cnt.get("shard.routed", 0) >= 2, cnt
    fanout = met["histograms"].get("shard.fanout", {})
    assert fanout.get("count", 0) >= 3 and fanout.get("max") == 2.0, fanout
    print(f"coordinator wire smoke OK: {scattered.n_total} solutions, "
          f"routed={cnt['shard.routed']} scattered={cnt['shard.scattered']} "
          f"shard_requests={cnt['shard.shard_requests']}")
EOF

kill -TERM "$SERVER_PID"
wait "$SERVER_PID" || true

# 3) the CLI front door reads the manifest transparently
OUT="$(python -m repro.launch.query --kg "$WORK/kg.shards.json" \
    'SELECT * WHERE { ?m <http://repro.org/vocab/gene_name> ?g } LIMIT 3' 2>&1)"
echo "$OUT" | grep -q "shards from" || { echo "$OUT"; exit 1; }
echo "shard smoke OK"
