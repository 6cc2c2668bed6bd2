"""TPC-H under the W3C Direct Mapping, made from a seed: the warehouse's
eight CSV tables, their RML mapping, and a plain reference of the KG.

TPC Benchmark H, Standard Specification v3.0.1 (tpc.org): clause 1.4
gives the eight tables, their 61 columns, primary keys and ten foreign
keys, one of them composite (``(l_partkey, l_suppkey)`` references
``partsupp(ps_partkey, ps_suppkey)``); clause 4.2 gives the table sizes
for a scale factor SF (4.2.5) and the value rules (4.2.2-4.2.3), among
them the bridge that ties each lineitem to one of its part's four
suppliers.  "A Direct Mapping of Relational Data to RDF", W3C
Recommendation 2012, maps each table to a class ``<table>``, each row to
``<table/k1=v1;k2=v2>`` from its primary key, each column to a literal
triple ``<table#col>`` and each foreign key to a reference triple
``<table#ref-c1;c2>``; R2RML 10.2 types the literals (INTEGER
xsd:integer, DECIMAL xsd:decimal, DATE xsd:date, text plain).  The
mapping here writes exactly that in RML: a composite foreign key is one
referencing object map with one ``rr:joinCondition`` a column.

What the configuration assumes (``bench/configs/tpch-dm-sf0.01.json``):
tables as CSV with a header row; text columns drawn from word lists in
the spirit of clause 4.2.2.10, not dbgen's grammar; decimals with two
places, typed as written; ISO dates.  Below the specification's smallest
scale factor the bridge can name one supplier twice for a part: there
the next supplier not yet taken is used, so a part keeps four distinct
suppliers (at SF 0.01 the bridge never collides).

As in ``gtfs.py``, the run's seed draws no new structure: the table
sizes and each order's number of lines come from seed 0, and the seed
renames the part, supplier and order keys (seeded permutations), draws
every other value and shuffles each table's rows.  Every row gives the
same number of triples whatever its values (no column is NULL), so every
seed does the same work.

The reference (``reference_lines``) reads nothing of the program: it
renders every triple from the generated rows and joins each foreign key
by a dict lookup on its column tuple.
"""

from __future__ import annotations

import csv
import dataclasses
import os

import numpy as np

from gtfs import iri_safe
from reference import escape_literal

BASE = "http://example.com/tpch/"
XSD = "http://www.w3.org/2001/XMLSchema#"
RDF_TYPE = "http://www.w3.org/1999/02/22-rdf-syntax-ns#type"

# Clause 1.4: each table's columns in order, with the R2RML 10.2 datatype
# of its SQL type (identifiers and integers xsd:integer, decimals
# xsd:decimal, dates xsd:date; None: CHAR or VARCHAR, a plain literal)
TABLES = {
    "part": (("p_partkey", "integer"), ("p_name", None), ("p_mfgr", None),
             ("p_brand", None), ("p_type", None), ("p_size", "integer"),
             ("p_container", None), ("p_retailprice", "decimal"), ("p_comment", None)),
    "supplier": (("s_suppkey", "integer"), ("s_name", None), ("s_address", None),
                 ("s_nationkey", "integer"), ("s_phone", None), ("s_acctbal", "decimal"),
                 ("s_comment", None)),
    "partsupp": (("ps_partkey", "integer"), ("ps_suppkey", "integer"),
                 ("ps_availqty", "integer"), ("ps_supplycost", "decimal"),
                 ("ps_comment", None)),
    "customer": (("c_custkey", "integer"), ("c_name", None), ("c_address", None),
                 ("c_nationkey", "integer"), ("c_phone", None), ("c_acctbal", "decimal"),
                 ("c_mktsegment", None), ("c_comment", None)),
    "orders": (("o_orderkey", "integer"), ("o_custkey", "integer"),
               ("o_orderstatus", None), ("o_totalprice", "decimal"),
               ("o_orderdate", "date"), ("o_orderpriority", None), ("o_clerk", None),
               ("o_shippriority", "integer"), ("o_comment", None)),
    "lineitem": (("l_orderkey", "integer"), ("l_partkey", "integer"),
                 ("l_suppkey", "integer"), ("l_linenumber", "integer"),
                 ("l_quantity", "decimal"), ("l_extendedprice", "decimal"),
                 ("l_discount", "decimal"), ("l_tax", "decimal"), ("l_returnflag", None),
                 ("l_linestatus", None), ("l_shipdate", "date"), ("l_commitdate", "date"),
                 ("l_receiptdate", "date"), ("l_shipinstruct", None), ("l_shipmode", None),
                 ("l_comment", None)),
    "nation": (("n_nationkey", "integer"), ("n_name", None), ("n_regionkey", "integer"),
               ("n_comment", None)),
    "region": (("r_regionkey", "integer"), ("r_name", None), ("r_comment", None)),
}
PRIMARY_KEYS = {
    "part": ("p_partkey",), "supplier": ("s_suppkey",),
    "partsupp": ("ps_partkey", "ps_suppkey"), "customer": ("c_custkey",),
    "orders": ("o_orderkey",), "lineitem": ("l_orderkey", "l_linenumber"),
    "nation": ("n_nationkey",), "region": ("r_regionkey",),
}
# clause 1.4.2.3: (table, columns, referenced table, referenced columns)
FOREIGN_KEYS = (
    ("supplier", ("s_nationkey",), "nation", ("n_nationkey",)),
    ("partsupp", ("ps_partkey",), "part", ("p_partkey",)),
    ("partsupp", ("ps_suppkey",), "supplier", ("s_suppkey",)),
    ("customer", ("c_nationkey",), "nation", ("n_nationkey",)),
    ("orders", ("o_custkey",), "customer", ("c_custkey",)),
    ("lineitem", ("l_orderkey",), "orders", ("o_orderkey",)),
    ("lineitem", ("l_partkey",), "part", ("p_partkey",)),
    ("lineitem", ("l_suppkey",), "supplier", ("s_suppkey",)),
    ("lineitem", ("l_partkey", "l_suppkey"), "partsupp", ("ps_partkey", "ps_suppkey")),
    ("nation", ("n_regionkey",), "region", ("r_regionkey",)),
)

STRUCTURE_SEED = 0
SUPPLIERS_PER_PART = 4
CURRENT_DATE = np.datetime64("1995-06-17")
START_DATE = np.datetime64("1992-01-01")
END_DATE = np.datetime64("1998-12-31")

# clause 4.2.3's fixed lists
REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
NATIONS = (("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
           ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
           ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
           ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
           ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
           ("UNITED STATES", 1))
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush brown "
    "burlywood burnished chartreuse chiffon chocolate coral cornflower cornsilk cream "
    "cyan dark deep dim dodger drab firebrick floral forest frosted gainsboro ghost "
    "goldenrod green grey honeydew hot indian ivory khaki lace lavender lawn lemon light "
    "lime linen magenta maroon medium metallic midnight mint misty moccasin navajo navy "
    "olive orange orchid pale papaya peach peru pink plum powder puff purple red rose "
    "rosy royal saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
TYPES = (("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"),
         ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"),
         ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER"))
CONTAINERS = (("SM", "LG", "MED", "JUMBO", "WRAP"),
              ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"))
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
# clause 4.2.2.10's parts of speech, for the comment columns
WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses platelets "
    "asymptotes courts dolphins multipliers sauternes warthogs frets dinos attainments "
    "somas patterns forges braids frays warhorses dugouts epitaphs pearls tithes waters "
    "orbits gifts sheaves depths sentiments decoys realms pains grouches escapades "
    "sleep wake are cajole haggle nag use boost affix detect integrate maintain nod was "
    "lose sublate solve thrash promise engage hinder print breach eat grow impress mold "
    "poach serve run dazzle snooze doze unwind kindle play hang believe doubt "
    "furious sly careful blithe quick fluffy slow quiet ruthless thin close dogged "
    "daring brave stealthy permanent enticing idle busy regular final ironic even bold "
    "silent sometimes always never furiously slyly carefully blithely quickly fluffily "
    "slowly quietly ruthlessly thinly closely doggedly daringly bravely stealthily "
    "permanently enticingly idly busily regularly finally ironically evenly boldly "
    "silently about above according across after against along alongside among around "
    "at atop before behind beneath beside besides between beyond by despite during "
    "except for from inside instead of into near of on outside over past since through "
    "throughout to toward under until up upon without with within").split()
# words a comment has, per table: about clause 4.2.3's text lengths
COMMENT_WORDS = {"part": (1, 3), "supplier": (4, 14), "partsupp": (7, 28),
                 "customer": (4, 16), "orders": (3, 11), "lineitem": (2, 6),
                 "nation": (5, 16), "region": (5, 16)}
ALNUM = np.array(list("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 ,"))


def columns(table: str) -> "tuple[str, ...]":
    return tuple(c for c, _t in TABLES[table])


def sizes(sf: float) -> "dict[str, int]":
    """Clause 4.2.5's rows of each table at scale factor ``sf``, but for
    lineitem, whose lines per order are drawn (``make``)."""
    n = {"supplier": round(sf * 10_000), "part": round(sf * 200_000),
         "customer": round(sf * 150_000), "orders": round(sf * 1_500_000)}
    n["partsupp"] = SUPPLIERS_PER_PART * n["part"]
    n.update(nation=len(NATIONS), region=len(REGIONS))
    return n


def lines_per_order(sf: float) -> np.ndarray:
    """Each order's lines, uniform in [1, 7] (clause 4.2.3), at seed 0."""
    g = np.random.default_rng(STRUCTURE_SEED)
    return g.integers(1, 8, sizes(sf)["orders"])


def part_suppliers(n_part: int, n_supp: int) -> np.ndarray:
    """``[n_part, 4]`` supplier keys of each part key 1..n_part: clause
    4.2.3's bridge ``(p + i * (S/4 + (p - 1) / S)) mod S + 1``, with the
    next free supplier where it names one twice (only below SF 1's range
    of S)."""
    if n_supp < SUPPLIERS_PER_PART:
        raise ValueError(f"{n_supp} suppliers cannot give each part four")
    out = np.empty((n_part, SUPPLIERS_PER_PART), np.int64)
    for p in range(1, n_part + 1):
        taken: list = []
        for i in range(SUPPLIERS_PER_PART):
            s = (p + i * (n_supp // 4 + (p - 1) // n_supp)) % n_supp + 1
            while s in taken:
                s = s % n_supp + 1
            taken.append(s)
        out[p - 1] = taken
    return out


def _money(cents: np.ndarray) -> "list[str]":
    return [f"{'-' if c < 0 else ''}{abs(c) // 100}.{abs(c) % 100:02d}" for c in cents.tolist()]


def _dates(days: np.ndarray) -> "list[str]":
    return [str(d) for d in START_DATE + days.astype("timedelta64[D]")]


def _pick(r, options, n) -> "list[str]":
    return [options[i] for i in r.integers(0, len(options), n).tolist()]


def _comments(r, table: str, n: int) -> "list[str]":
    """Word-list sentences; about a third carry a comma."""
    lo, hi = COMMENT_WORDS[table]
    count = r.integers(lo, hi + 1, n).tolist()
    words = r.integers(0, len(WORDS), (n, hi)).tolist()
    comma = (r.random(n) < 1 / 3).tolist()
    out = []
    for k, ws, c in zip(count, words, comma):
        ws = [WORDS[w] for w in ws[:k]]
        if c and k > 1:
            ws[0] += ","
        out.append(" ".join(ws))
    return out


def _addresses(r, n: int) -> "list[str]":
    """Clause 4.2.2.7's v-strings: 10 to 40 random characters."""
    lengths = r.integers(10, 41, n).tolist()
    chars = ALNUM[r.integers(0, len(ALNUM), (n, 40))]
    return ["".join(row[:k]).strip() or "x" for row, k in zip(chars.tolist(), lengths)]


def _phones(r, nations: np.ndarray) -> "list[str]":
    a, b, c = r.integers(100, 1000, len(nations)), r.integers(100, 1000, len(nations)), \
        r.integers(1000, 10000, len(nations))
    return [f"{n + 10}-{x}-{y}-{z}" for n, x, y, z in
            zip(nations.tolist(), a.tolist(), b.tolist(), c.tolist())]


@dataclasses.dataclass
class Testbed:
    """The warehouse: each table as columns of strings."""

    tables: "dict[str, dict[str, list[str]]]"

    @property
    def source_rows(self) -> int:
        return sum(len(t[next(iter(t))]) for t in self.tables.values())

    def write(self, out_dir: str) -> str:
        """Write the CSVs and the RML mapping; returns the mapping path."""
        os.makedirs(out_dir, exist_ok=True)
        for name, table in self.tables.items():
            with open(os.path.join(out_dir, f"{name}.csv"), "w", newline="",
                      encoding="utf-8") as f:
                w = csv.writer(f, lineterminator="\n")
                w.writerow(columns(name))
                w.writerows(zip(*(table[c] for c in columns(name))))
        path = os.path.join(out_dir, "mapping.ttl")
        with open(path, "w", encoding="utf-8") as f:
            f.write(mapping_turtle())
        return path


def make(sf: float, seed: int) -> Testbed:
    """The warehouse at scale factor ``sf``, renamed and shuffled by
    ``seed``."""
    n = sizes(sf)
    r = np.random.default_rng([seed, 0x7C9B])
    lines = lines_per_order(sf)
    part_key = r.permutation(n["part"]) + 1          # part index -> its key
    supp_key = r.permutation(n["supplier"]) + 1      # supplier index -> its key
    # dbgen's sparse order keys: the first 8 of every 32
    sparse = np.arange(n["orders"])
    order_key = (sparse // 8 * 32 + sparse % 8 + 1)[r.permutation(n["orders"])]
    bridge = part_suppliers(n["part"], n["supplier"]) - 1  # part index -> supplier indexes
    t = {}

    t["region"] = {"r_regionkey": [str(k) for k in range(len(REGIONS))],
                   "r_name": list(REGIONS), "r_comment": _comments(r, "region", len(REGIONS))}
    t["nation"] = {"n_nationkey": [str(k) for k in range(len(NATIONS))],
                   "n_name": [nm for nm, _r in NATIONS],
                   "n_regionkey": [str(rk) for _n, rk in NATIONS],
                   "n_comment": _comments(r, "nation", len(NATIONS))}

    k = n["supplier"]
    nations = r.integers(0, len(NATIONS), k)
    keys = supp_key
    t["supplier"] = {
        "s_suppkey": [str(x) for x in keys.tolist()],
        "s_name": [f"Supplier#{x:09d}" for x in keys.tolist()],
        "s_address": _addresses(r, k), "s_nationkey": [str(x) for x in nations.tolist()],
        "s_phone": _phones(r, nations), "s_acctbal": _money(r.integers(-99_999, 1_000_000, k)),
        "s_comment": _comments(r, "supplier", k),
    }

    k = n["part"]
    keys = part_key
    # P_RETAILPRICE = (90000 + ((P_PARTKEY/10) modulo 20001) + 100 * (P_PARTKEY modulo 1000))/100
    price = 90_000 + (keys // 10) % 20_001 + 100 * (keys % 1000)  # cents
    names = [" ".join(COLORS[c] for c in r.choice(len(COLORS), 5, replace=False).tolist())
             for _ in range(k)]
    mfgr = r.integers(1, 6, k)
    t["part"] = {
        "p_partkey": [str(x) for x in keys.tolist()], "p_name": names,
        "p_mfgr": [f"Manufacturer#{m}" for m in mfgr.tolist()],
        "p_brand": [f"Brand#{m}{b}" for m, b in zip(mfgr.tolist(), r.integers(1, 6, k).tolist())],
        "p_type": [" ".join(w) for w in zip(*(_pick(r, s, k) for s in TYPES))],
        "p_size": [str(x) for x in r.integers(1, 51, k).tolist()],
        "p_container": [" ".join(w) for w in zip(*(_pick(r, s, k) for s in CONTAINERS))],
        "p_retailprice": _money(price), "p_comment": _comments(r, "part", k),
    }

    k = n["partsupp"]
    ps_part = np.repeat(np.arange(n["part"]), SUPPLIERS_PER_PART)
    ps_supp = bridge.reshape(-1)
    t["partsupp"] = {
        "ps_partkey": [str(x) for x in part_key[ps_part].tolist()],
        "ps_suppkey": [str(x) for x in supp_key[ps_supp].tolist()],
        "ps_availqty": [str(x) for x in r.integers(1, 10_000, k).tolist()],
        "ps_supplycost": _money(r.integers(100, 100_001, k)),
        "ps_comment": _comments(r, "partsupp", k),
    }

    k = n["customer"]
    keys = r.permutation(k) + 1
    nations = r.integers(0, len(NATIONS), k)
    t["customer"] = {
        "c_custkey": [str(x) for x in keys.tolist()],
        "c_name": [f"Customer#{x:09d}" for x in keys.tolist()],
        "c_address": _addresses(r, k), "c_nationkey": [str(x) for x in nations.tolist()],
        "c_phone": _phones(r, nations), "c_acctbal": _money(r.integers(-99_999, 1_000_000, k)),
        "c_mktsegment": _pick(r, SEGMENTS, k), "c_comment": _comments(r, "customer", k),
    }

    # lineitem: order o has lines[o] lines; each a part and one of its four
    # suppliers, and clause 4.2.3's prices, dates and flags
    k = int(lines.sum())
    order = np.repeat(np.arange(n["orders"]), lines)
    linenumber = np.arange(k) - np.repeat(np.cumsum(lines) - lines, lines) + 1
    part = r.integers(0, n["part"], k)
    supp = bridge[part, r.integers(0, SUPPLIERS_PER_PART, k)]
    orderdate = r.integers(0, int((END_DATE - 151 - START_DATE).astype(int)) + 1, n["orders"])
    quantity = r.integers(1, 51, k)
    extended = quantity * price[part]                       # cents
    discount = r.integers(0, 11, k)                         # hundredths
    tax = r.integers(0, 9, k)
    shipdate = orderdate[order] + r.integers(1, 122, k)
    commitdate = orderdate[order] + r.integers(30, 91, k)
    receiptdate = shipdate + r.integers(1, 31, k)
    current = int((CURRENT_DATE - START_DATE).astype(int))
    returned = np.where(r.random(k) < 0.5, "R", "A")
    t["lineitem"] = {
        "l_orderkey": [str(x) for x in order_key[order].tolist()],
        "l_partkey": [str(x) for x in part_key[part].tolist()],
        "l_suppkey": [str(x) for x in supp_key[supp].tolist()],
        "l_linenumber": [str(x) for x in linenumber.tolist()],
        "l_quantity": [f"{x}.00" for x in quantity.tolist()],
        "l_extendedprice": _money(extended),
        "l_discount": [f"0.{x:02d}" for x in discount.tolist()],
        "l_tax": [f"0.{x:02d}" for x in tax.tolist()],
        "l_returnflag": np.where(receiptdate <= current, returned, "N").tolist(),
        "l_linestatus": np.where(shipdate > current, "O", "F").tolist(),
        "l_shipdate": _dates(shipdate), "l_commitdate": _dates(commitdate),
        "l_receiptdate": _dates(receiptdate),
        "l_shipinstruct": _pick(r, INSTRUCTIONS, k), "l_shipmode": _pick(r, MODES, k),
        "l_comment": _comments(r, "lineitem", k),
    }

    k = n["orders"]
    # O_TOTALPRICE = sum(L_EXTENDEDPRICE * (1 + L_TAX) * (1 - L_DISCOUNT)),
    # in cents, rounded half up
    charge = extended * (100 + tax) * (100 - discount)      # cents x 10^4
    total = (np.bincount(order, weights=charge, minlength=k) / 1e4 + 0.5).astype(np.int64)
    open_lines = np.bincount(order, weights=shipdate > current, minlength=k)
    status = np.where(open_lines == lines, "O", np.where(open_lines == 0, "F", "P"))
    eligible = np.arange(1, n["customer"] + 1)
    eligible = eligible[eligible % 3 != 0]   # a third of the customers place no order
    clerks = max(round(sf * 1000), 1)
    t["orders"] = {
        "o_orderkey": [str(x) for x in order_key.tolist()],
        "o_custkey": [str(x) for x in eligible[r.integers(0, len(eligible), k)].tolist()],
        "o_orderstatus": status.tolist(), "o_totalprice": _money(total),
        "o_orderdate": _dates(orderdate), "o_orderpriority": _pick(r, PRIORITIES, k),
        "o_clerk": [f"Clerk#{x:09d}" for x in r.integers(1, clerks + 1, k).tolist()],
        "o_shippriority": ["0"] * k, "o_comment": _comments(r, "orders", k),
    }

    # the seed shuffles each table's rows
    for name, table in t.items():
        order_ = r.permutation(len(table[columns(name)[0]])).tolist()
        t[name] = {c: [table[c][j] for j in order_] for c in columns(name)}
    return Testbed({name: t[name] for name in TABLES})


# -- the Direct Mapping, as RML ---------------------------------------------------

_PREFIXES = """@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
"""


def row_template(table: str) -> str:
    """The row node: ``<table/k1={k1};k2={k2}>`` over the primary key."""
    return BASE + table + "/" + ";".join(f"{c}={{{c}}}" for c in PRIMARY_KEYS[table])


def literal_property(table: str, col: str) -> str:
    return f"{BASE}{table}#{col}"


def reference_property(table: str, cols: "tuple[str, ...]") -> str:
    return f"{BASE}{table}#ref-{';'.join(cols)}"


def mapping_turtle() -> str:
    """The Direct Mapping of the eight tables, in RML Turtle."""
    blocks = [_PREFIXES]
    for table in TABLES:
        lines = [
            f'rml:logicalSource [ rml:source "{table}.csv" ; rml:referenceFormulation ql:CSV ]',
            f'rr:subjectMap [ rr:template "{row_template(table)}" ; rr:class <{BASE}{table}> ]',
        ]
        for col, dtype in TABLES[table]:
            om = f'rml:reference "{col}"' + (f" ; rr:datatype <{XSD}{dtype}>" if dtype else "")
            lines.append(f"rr:predicateObjectMap [ rr:predicate <{literal_property(table, col)}> ; "
                         f"rr:objectMap [ {om} ] ]")
        for child, cols, parent, pcols in FOREIGN_KEYS:
            if child != table:
                continue
            conds = " ; ".join(f'rr:joinCondition [ rr:child "{c}" ; rr:parent "{p}" ]'
                               for c, p in zip(cols, pcols))
            lines.append(f"rr:predicateObjectMap [ rr:predicate <{reference_property(table, cols)}> ; "
                         f"rr:objectMap [ rr:parentTriplesMap <#{parent}> ; {conds} ] ]")
        blocks.append(f"<#{table}> a rr:TriplesMap ;\n    " + " ;\n    ".join(lines) + " .\n")
    return "\n".join(blocks)


# -- the reference ----------------------------------------------------------------


def _row_nodes(tb: Testbed, table: str) -> "list[str]":
    t = tb.tables[table]
    keys = PRIMARY_KEYS[table]
    return ["<" + BASE + table + "/" + ";".join(f"{c}={iri_safe(v)}" for c, v in zip(keys, vals))
            + ">" for vals in zip(*(t[c] for c in keys))]


def reference_lines(tb: Testbed, conditions: "int | None" = None) -> np.ndarray:
    """The KG as sorted distinct ``"<s> <p> <o>"`` lines.  With
    ``conditions=1`` (the control) a foreign key joins on its first
    column alone, so a composite key matches every row that shares its
    first part."""
    nodes = {table: _row_nodes(tb, table) for table in TABLES}
    out = set()
    for table, cols in TABLES.items():
        t = tb.tables[table]
        subj = nodes[table]
        out.update(f"{s} <{RDF_TYPE}> <{BASE}{table}>" for s in subj)
        for col, dtype in cols:
            suffix = f"^^<{XSD}{dtype}>" if dtype else ""
            pred = f"<{literal_property(table, col)}>"
            out.update(f'{s} {pred} "{escape_literal(v)}"{suffix}'
                       for s, v in zip(subj, t[col]) if v != "")
    for table, cols, parent, pcols in FOREIGN_KEYS:
        pred = f"<{reference_property(table, cols)}>"
        index: dict = {}
        for key, node in zip(zip(*(tb.tables[parent][c] for c in pcols[:conditions])),
                             nodes[parent]):
            if "" not in key:
                index.setdefault(key, []).append(node)
        for key, s in zip(zip(*(tb.tables[table][c] for c in cols[:conditions])), nodes[table]):
            out.update(f"{s} {pred} {o}" for o in index.get(key, ()))
    return np.array(sorted(out))
