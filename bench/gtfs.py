"""GTFS-Madrid-Bench, made from a seed: the feed's CSV tables, its RML
mapping to Linked GTFS, and a plain reference of the KG it gives.

Chaves-Fraga et al., "GTFS-Madrid-Bench: A benchmark for virtual knowledge
graph access in the transport domain", Journal of Web Semantics 65 (2020),
github.com/oeg-upm/gtfs-bench.  The benchmark scales the Madrid Metro GTFS
feed with VIG and maps its CSV distribution with RML to the Linked GTFS
vocabulary.  Neither the feed nor the published mapping is copied here:
the ten tables carry the GTFS reference columns (gtfs.org) the mapping
reads, their sizes follow ``stop_times`` by the ratios of
``bench/configs/gtfs-madrid-100k.json`` (``assumed``), and the mapping is
written from the published one's structure: one triples map per table,
Shape/ShapePoint and Service/CalendarRule over one source each and linked
by a referencing object map, and each GTFS foreign key a join.

As in ``testbed.py``, the run's seed does not draw a new structure: which
cells are empty, which stops a trip calls at and every fan-out come from
seed 0, and the seed renames the identifiers (seeded permutations) and
shuffles each table's rows.  So every seed does the same work.

The reference (``reference_lines``) reads nothing of the program: it
renders every triple itself, skipping a term whose cells are empty (NULL),
typing literals, making template values IRI-safe, and keeping a set.
"""

from __future__ import annotations

import csv
import dataclasses
import os
import re

import numpy as np

from reference import escape_literal

BASE = "http://transport.linkeddata.es/madrid/metro/"
RES = "http://transport.linkeddata.es/resource/"
GTFS = "http://vocab.gtfs.org/terms#"
XSD = "http://www.w3.org/2001/XMLSchema#"
FOAF = "http://xmlns.com/foaf/0.1/"
DCT = "http://purl.org/dc/terms/"
GEO = "http://www.w3.org/2003/01/geo/wgs84_pos#"
SCHEMA = "http://schema.org/"
RDF_TYPE = "http://www.w3.org/1999/02/22-rdf-syntax-ns#type"

STOPS_PER_TRIP = 20
POINTS_PER_SHAPE = 250
DAYS = ("monday", "tuesday", "wednesday", "thursday", "friday", "saturday", "sunday")

# the GTFS reference's columns of each file, in its order
COLUMNS = {
    "agency": ("agency_id", "agency_name", "agency_url", "agency_timezone",
               "agency_lang", "agency_phone", "agency_fare_url"),
    "feed_info": ("feed_publisher_name", "feed_publisher_url", "feed_lang",
                  "feed_start_date", "feed_end_date", "feed_version"),
    "routes": ("route_id", "agency_id", "route_short_name", "route_long_name",
               "route_desc", "route_type", "route_url", "route_color", "route_text_color"),
    "trips": ("route_id", "service_id", "trip_id", "trip_headsign", "trip_short_name",
              "direction_id", "block_id", "shape_id", "wheelchair_accessible"),
    "stop_times": ("trip_id", "arrival_time", "departure_time", "stop_id", "stop_sequence",
                   "stop_headsign", "pickup_type", "drop_off_type", "shape_dist_traveled"),
    "stops": ("stop_id", "stop_code", "stop_name", "stop_desc", "stop_lat", "stop_lon",
              "zone_id", "stop_url", "location_type", "parent_station", "wheelchair_boarding"),
    "shapes": ("shape_id", "shape_pt_lat", "shape_pt_lon", "shape_pt_sequence",
               "shape_dist_traveled"),
    "calendar": ("service_id",) + DAYS + ("start_date", "end_date"),
    "calendar_dates": ("service_id", "date", "exception_type"),
    "frequencies": ("trip_id", "start_time", "end_time", "headway_secs", "exact_times"),
}

# The mapping: (triples map, table, subject template under BASE, class,
# object maps).  An object map is (predicate, kind, argument, datatype):
# "lit" a reference (a typed literal where the datatype is given), "url" a
# reference with rr:termType rr:IRI, "enum" an IRI template under RES,
# "orm" the subject of another map over the same table, and "join" the
# subjects of a parent map whose (child, parent) columns are equal.
MAPS = (
    ("StopTime", "stop_times", "stoptimes/{trip_id}-{stop_id}-{arrival_time}", "StopTime", (
        (GTFS + "arrivalTime", "lit", "arrival_time", "duration"),
        (GTFS + "departureTime", "lit", "departure_time", "duration"),
        (GTFS + "stopSequence", "lit", "stop_sequence", "integer"),
        (GTFS + "headsign", "lit", "stop_headsign", None),
        (GTFS + "pickupType", "enum", "PickupType/{pickup_type}", None),
        (GTFS + "dropOffType", "enum", "DropOffType/{drop_off_type}", None),
        (GTFS + "distanceTraveled", "lit", "shape_dist_traveled", "double"),
        (GTFS + "trip", "join", "Trip", ("trip_id", "trip_id")),
        (GTFS + "stop", "join", "Stop", ("stop_id", "stop_id")),
    )),
    ("Trip", "trips", "trips/{trip_id}", "Trip", (
        (GTFS + "route", "join", "Route", ("route_id", "route_id")),
        (GTFS + "service", "join", "Service", ("service_id", "service_id")),
        (GTFS + "headsign", "lit", "trip_headsign", None),
        (GTFS + "shortName", "lit", "trip_short_name", None),
        (GTFS + "direction", "lit", "direction_id", None),
        (GTFS + "block", "lit", "block_id", None),
        (GTFS + "shape", "join", "Shape", ("shape_id", "shape_id")),
        (GTFS + "wheelchairAccessible", "enum",
         "WheelchairBoardingStatus/{wheelchair_accessible}", None),
    )),
    ("Route", "routes", "routes/{route_id}", "Route", (
        (GTFS + "agency", "join", "Agency", ("agency_id", "agency_id")),
        (GTFS + "shortName", "lit", "route_short_name", None),
        (GTFS + "longName", "lit", "route_long_name", None),
        (DCT + "description", "lit", "route_desc", None),
        (GTFS + "routeType", "enum", "RouteType/{route_type}", None),
        (GTFS + "routeUrl", "url", "route_url", None),
        (GTFS + "color", "lit", "route_color", None),
        (GTFS + "textColor", "lit", "route_text_color", None),
    )),
    ("Agency", "agency", "agency/{agency_id}", "Agency", (
        (FOAF + "name", "lit", "agency_name", None),
        (FOAF + "page", "url", "agency_url", None),
        (GTFS + "timeZone", "lit", "agency_timezone", None),
        (DCT + "language", "lit", "agency_lang", None),
        (FOAF + "phone", "lit", "agency_phone", None),
        (GTFS + "fareUrl", "url", "agency_fare_url", None),
    )),
    ("Stop", "stops", "stops/{stop_id}", "Stop", (
        (GTFS + "code", "lit", "stop_code", None),
        (FOAF + "name", "lit", "stop_name", None),
        (DCT + "description", "lit", "stop_desc", None),
        (GEO + "lat", "lit", "stop_lat", "double"),
        (GEO + "long", "lit", "stop_lon", "double"),
        (GTFS + "zone", "lit", "zone_id", None),
        (FOAF + "page", "url", "stop_url", None),
        (GTFS + "locationType", "enum", "LocationType/{location_type}", None),
        (GTFS + "parentStation", "join", "Stop", ("parent_station", "stop_id")),
        (GTFS + "wheelchairAccessible", "enum",
         "WheelchairBoardingStatus/{wheelchair_boarding}", None),
    )),
    ("Shape", "shapes", "shape/{shape_id}", "Shape", (
        (GTFS + "shapePoint", "orm", "ShapePoint", None),
    )),
    ("ShapePoint", "shapes", "shape_point/{shape_id}-{shape_pt_sequence}", "ShapePoint", (
        (GEO + "lat", "lit", "shape_pt_lat", "double"),
        (GEO + "long", "lit", "shape_pt_lon", "double"),
        (GTFS + "pointSequence", "lit", "shape_pt_sequence", "integer"),
        (GTFS + "distanceTraveled", "lit", "shape_dist_traveled", "double"),
    )),
    ("Service", "calendar", "services/{service_id}", "Service", (
        (GTFS + "serviceRule", "orm", "CalendarRule", None),
    )),
    ("CalendarRule", "calendar", "calendar_rules/{service_id}", "CalendarRule",
     tuple((GTFS + day, "lit", day, "boolean") for day in DAYS) + (
        (SCHEMA + "startDate", "lit", "start_date", "date"),
        (SCHEMA + "endDate", "lit", "end_date", "date"),
    )),
    ("CalendarDateRule", "calendar_dates", "calendar_date_rule/{service_id}-{date}",
     "CalendarDateRule", (
        (GTFS + "service", "join", "Service", ("service_id", "service_id")),
        (DCT + "date", "lit", "date", "date"),
        (GTFS + "dateAddition", "enum", "ExceptionType/{exception_type}", None),
    )),
    ("Frequency", "frequencies", "frequency/{trip_id}-{start_time}", "Frequency", (
        (GTFS + "trip", "join", "Trip", ("trip_id", "trip_id")),
        (GTFS + "startTime", "lit", "start_time", "duration"),
        (GTFS + "endTime", "lit", "end_time", "duration"),
        (GTFS + "headwaySeconds", "lit", "headway_secs", "integer"),
        (GTFS + "exactTimes", "lit", "exact_times", "boolean"),
    )),
    ("Feed", "feed_info", "feed/{feed_publisher_name}", "Feed", (
        (DCT + "publisher", "lit", "feed_publisher_name", None),
        (FOAF + "page", "url", "feed_publisher_url", None),
        (DCT + "language", "lit", "feed_lang", None),
        (SCHEMA + "startDate", "lit", "feed_start_date", "date"),
        (SCHEMA + "endDate", "lit", "feed_end_date", "date"),
        (SCHEMA + "version", "lit", "feed_version", None),
    )),
)

_NAMES = ("Sol", "Ópera", "Callao", "Gran Vía", "Tribunal", "Alonso Martínez",
          "Núñez de Balboa", "Cuatro Caminos", "Argüelles", "Príncipe Pío",
          "Plaza de España", "Moncloa", "Atocha", "Legazpi", "Embajadores",
          "Lavapiés", "Tirso de Molina", "Antón Martín", "Colón", "Chamartín")
AGENCY = "CRTM"
PUBLISHER = "Consorcio Regional de Transportes de Madrid"
STRUCTURE_SEED = 0


def sizes(rows: int) -> "dict[str, int]":
    """Rows of each table for ``rows`` stop_times rows: the ratios of the
    configuration's ``assumed``, with small floors."""
    return {
        "stop_times": rows,
        "trips": max(-(-rows // STOPS_PER_TRIP), 2),
        "stops": max(rows // 50, 6),
        "shapes": max(rows // (10 * POINTS_PER_SHAPE), 2) * POINTS_PER_SHAPE,
        "routes": max(rows // 5000, 2),
        "calendar": max(rows // 25000, 2),
        "calendar_dates": max(rows // 500, 4),
        "frequencies": max(rows // 200, 4),
        "agency": 1,
        "feed_info": 1,
    }


def _hms(seconds: np.ndarray) -> "list[str]":
    return [f"{s // 3600:02d}:{s // 60 % 60:02d}:{s % 60:02d}" for s in seconds.tolist()]


def _fmt(values: np.ndarray, spec: str) -> "list[str]":
    return [format(v, spec) for v in values.tolist()]


def _some(values: "list[str]", keep: np.ndarray) -> "list[str]":
    """``values`` with the cells where ``keep`` is False left empty."""
    return [v if k else "" for v, k in zip(values, keep.tolist())]


def _date(days: np.ndarray) -> "list[str]":
    d = np.datetime64("2017-01-01") + days.astype("timedelta64[D]")
    return [str(x) for x in d]


@dataclasses.dataclass
class Testbed:
    """The feed: each table as columns of strings, "" for an empty cell."""

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
                w.writerow(COLUMNS[name])
                w.writerows(zip(*(table[c] for c in COLUMNS[name])))
        path = os.path.join(out_dir, "mapping.ttl")
        with open(path, "w", encoding="utf-8") as f:
            f.write(mapping_turtle())
        return path


def make(rows: int, seed: int) -> Testbed:
    """The feed for ``rows`` stop_times rows, renamed and shuffled by
    ``seed``."""
    n = sizes(rows)
    g = np.random.default_rng(STRUCTURE_SEED)
    r = np.random.default_rng([seed, 0x67F5])
    n_st = max(n["stops"] // 3, 1)
    n_pl = n["stops"] - n_st
    n_shapes = n["shapes"] // POINTS_PER_SHAPE

    def ids(prefix: str, count: int) -> np.ndarray:
        return np.array([f"{prefix}{k}" for k in r.permutation(count).tolist()], dtype=object)

    trip_ids, route_ids = ids("T", n["trips"]), ids("R", n["routes"])
    service_ids, shape_ids = ids("S", n["calendar"]), ids("SH", n_shapes)
    stop_ids = np.concatenate([ids("est_", n_st), ids("par_", n_pl)])
    station_names = [f"{_NAMES[k % len(_NAMES)]} {k // len(_NAMES) + 1}" for k in range(n_st)]
    t = {}

    # stop_times: trip k calls at 20 platforms, two minutes apart
    i = np.arange(rows)
    trip, seq = i // STOPS_PER_TRIP, i % STOPS_PER_TRIP + 1
    start = g.integers(5 * 3600, 23 * 3600, size=n["trips"]) // 60 * 60
    arrive = start[trip] + 120 * seq
    stop = n_st + g.integers(0, n_pl, size=rows)
    t["stop_times"] = {
        "trip_id": list(trip_ids[trip]), "arrival_time": _hms(arrive),
        "departure_time": _hms(arrive + 20), "stop_id": list(stop_ids[stop]),
        "stop_sequence": [str(s) for s in seq.tolist()],
        "stop_headsign": _some([station_names[k % n_st] for k in trip.tolist()],
                               g.random(rows) < 0.10),
        "pickup_type": _fmt(g.integers(0, 4, rows), "d"),
        "drop_off_type": _fmt(g.integers(0, 4, rows), "d"),
        "shape_dist_traveled": _some(_fmt(612.5 * (seq - 1), ".1f"), g.random(rows) < 0.5),
    }
    k = np.arange(n["trips"])
    t["trips"] = {
        "route_id": list(route_ids[g.integers(0, n["routes"], k.size)]),
        "service_id": list(service_ids[g.integers(0, n["calendar"], k.size)]),
        "trip_id": list(trip_ids), "trip_headsign": [station_names[j % n_st] for j in k.tolist()],
        "trip_short_name": [f"{j % 1000:03d}" for j in k.tolist()],
        "direction_id": _fmt(k % 2, "d"),
        "block_id": _some([f"B{j // 4}" for j in k.tolist()], g.random(k.size) < 0.5),
        "shape_id": list(shape_ids[g.integers(0, n_shapes, k.size)]),
        "wheelchair_accessible": _fmt(g.integers(0, 3, k.size), "d"),
    }
    # stops: a third are stations, the rest platforms of a station
    k = np.arange(n["stops"])
    station = np.where(k < n_st, k, g.integers(0, n_st, k.size))
    names = [station_names[s] if j < n_st else f"{station_names[s]}, andén {j}"
             for j, s in zip(k.tolist(), station.tolist())]
    t["stops"] = {
        "stop_id": list(stop_ids), "stop_code": _fmt(1000 + k, "d"), "stop_name": names,
        "stop_desc": _some([f'Acceso "{nm}"' for nm in names], g.random(k.size) < 0.5),
        "stop_lat": _fmt(40.4168 + 0.05 * g.standard_normal(k.size), ".6f"),
        "stop_lon": _fmt(-3.7038 + 0.05 * g.standard_normal(k.size), ".6f"),
        "zone_id": [("A", "B1", "B2")[j % 3] for j in k.tolist()],
        "stop_url": _some([f"http://www.crtm.es/stops/{s}" for s in stop_ids],
                          g.random(k.size) < 0.5),
        "location_type": ["1" if j < n_st else "0" for j in k.tolist()],
        "parent_station": [""] * n_st + list(stop_ids[station[n_st:]]),
        "wheelchair_boarding": _fmt(g.integers(0, 3, k.size), "d"),
    }
    k = np.arange(n["shapes"])
    t["shapes"] = {
        "shape_id": list(shape_ids[k // POINTS_PER_SHAPE]),
        "shape_pt_lat": _fmt(40.4168 + 0.05 * g.standard_normal(k.size), ".6f"),
        "shape_pt_lon": _fmt(-3.7038 + 0.05 * g.standard_normal(k.size), ".6f"),
        "shape_pt_sequence": _fmt(k % POINTS_PER_SHAPE + 1, "d"),
        "shape_dist_traveled": _some(_fmt(40.0 * (k % POINTS_PER_SHAPE), ".1f"),
                                     g.random(k.size) < 0.5),
    }
    k = np.arange(n["routes"])
    t["routes"] = {
        "route_id": list(route_ids), "agency_id": [AGENCY] * k.size,
        "route_short_name": [f"L{j + 1}" for j in k.tolist()],
        "route_long_name": [f"{station_names[j % n_st]} - {station_names[-1 - j % n_st]}"
                            for j in k.tolist()],
        "route_desc": _some([f"Línea {j + 1}" for j in k.tolist()], g.random(k.size) < 0.5),
        "route_type": _fmt(g.choice([1, 1, 1, 2, 3], k.size), "d"),
        "route_url": _some([f"http://www.metromadrid.es/es/linea/{s}" for s in route_ids],
                           g.random(k.size) < 0.5),
        "route_color": _fmt(g.integers(0, 1 << 24, k.size), "06X"),
        "route_text_color": ["FFFFFF"] * k.size,
    }
    k = np.arange(n["calendar"])
    t["calendar"] = dict(
        {"service_id": list(service_ids)},
        **{day: _fmt(g.integers(0, 2, k.size), "d") for day in DAYS},
        start_date=_date(np.zeros(k.size, int)), end_date=_date(np.full(k.size, 364)),
    )
    k = np.arange(n["calendar_dates"])
    t["calendar_dates"] = {
        "service_id": list(service_ids[k % n["calendar"]]),
        "date": _date(k // n["calendar"]), "exception_type": _fmt(1 + k % 2, "d"),
    }
    k = np.arange(n["frequencies"])
    fstart = 6 * 3600 + 3600 * (k // n["trips"])
    t["frequencies"] = {
        "trip_id": list(trip_ids[k % n["trips"]]), "start_time": _hms(fstart),
        "end_time": _hms(fstart + 3600),
        "headway_secs": _fmt(g.choice([120, 180, 240, 300, 420, 600], k.size), "d"),
        "exact_times": _fmt(g.integers(0, 2, k.size), "d"),
    }
    t["agency"] = {
        "agency_id": [AGENCY], "agency_name": [PUBLISHER],
        "agency_url": ["http://www.crtm.es"], "agency_timezone": ["Europe/Madrid"],
        "agency_lang": ["es"], "agency_phone": ["+34 902 444 403"], "agency_fare_url": [""],
    }
    t["feed_info"] = {
        "feed_publisher_name": [PUBLISHER], "feed_publisher_url": ["http://www.crtm.es"],
        "feed_lang": ["es"], "feed_start_date": ["2017-01-01"],
        "feed_end_date": ["2017-12-31"], "feed_version": ["20170101"],
    }
    # the seed shuffles each table's rows
    for name, table in t.items():
        order = r.permutation(len(table[COLUMNS[name][0]])).tolist()
        t[name] = {c: [table[c][j] for j in order] for c in COLUMNS[name]}
    return Testbed(t)


# -- the mapping --------------------------------------------------------------

_PREFIXES = """@prefix rr: <http://www.w3.org/ns/r2rml#> .
@prefix rml: <http://semweb.mmlab.be/ns/rml#> .
@prefix ql: <http://semweb.mmlab.be/ns/ql#> .
"""


def _object_map(kind: str, arg, datatype) -> str:
    if kind == "lit":
        out = f'rml:reference "{arg}"'
        return out + (f" ; rr:datatype <{XSD}{datatype}>" if datatype else "")
    if kind == "url":
        return f'rml:reference "{arg}" ; rr:termType rr:IRI'
    if kind == "enum":
        return f'rr:template "{RES}{arg}"'
    if kind == "orm":
        return f"rr:parentTriplesMap <#{arg}>"
    child, parent = datatype
    return (f'rr:parentTriplesMap <#{arg}> ; rr:joinCondition [ rr:child "{child}" ; '
            f'rr:parent "{parent}" ]')


def mapping_turtle() -> str:
    """The RML mapping of the feed, in Turtle."""
    blocks = [_PREFIXES]
    for name, table, subject, cls, poms in MAPS:
        lines = [
            f'rml:logicalSource [ rml:source "{table}.csv" ; rml:referenceFormulation ql:CSV ]',
            f'rr:subjectMap [ rr:template "{BASE}{subject}" ; rr:class <{GTFS}{cls}> ]',
        ]
        for pred, kind, arg, extra in poms:
            lines.append(f"rr:predicateObjectMap [ rr:predicate <{pred}> ; "
                         f"rr:objectMap [ {_object_map(kind, arg, extra)} ] ]")
        blocks.append(f"<#{name}> a rr:TriplesMap ;\n    " + " ;\n    ".join(lines) + " .\n")
    return "\n".join(blocks)


# -- the reference --------------------------------------------------------------

# RFC 3987 iunreserved: ALPHA / DIGIT / "-" / "." / "_" / "~" / ucschar
_UCS = ("\u00a0-\ud7ff\uf900-\ufdcf\ufdf0-\uffef"
        + "".join(chr(p << 16) + "-" + chr((p << 16) | 0xFFFD) for p in range(1, 14))
        + "\U000e1000-\U000efffd")
_UNSAFE = re.compile("[^A-Za-z0-9\\-._~" + _UCS + "]")
_SLOT = re.compile(r"\{([^{}]+)\}")


def iri_safe(value: str) -> str:
    """R2RML §7.3: each character outside iunreserved as %HH of its UTF-8."""
    return _UNSAFE.sub(lambda m: "".join(f"%{b:02X}" for b in m.group(0).encode()), value)


def _template(tpl: str, table, n: int, empty_is_null: bool) -> "list[str | None]":
    """An IRI template over every row: None where a slot's cell is empty
    (NULL), unless ``empty_is_null`` is False."""
    chunks = _SLOT.split(tpl)  # text, column, text, column, ..., text
    cols = [table[c] for c in chunks[1::2]]
    out = []
    for i in range(n):
        vals = [c[i] for c in cols]
        if empty_is_null and "" in vals:
            out.append(None)
            continue
        parts = [chunks[0]]
        for v, text in zip(vals, chunks[2::2]):
            parts += [iri_safe(v), text]
        out.append("<" + "".join(parts) + ">")
    return out


def _objects(kind: str, arg, datatype, table, n: int, empty_is_null: bool):
    """The object term of a non-join object map on every row, or None."""
    if kind == "enum":
        return _template(RES + arg, table, n, empty_is_null)
    suffix = f"^^<{XSD}{datatype}>" if datatype else ""
    out = []
    for v in table[arg]:
        if v == "" and empty_is_null:
            out.append(None)
        elif kind == "url":
            out.append(f"<{v}>")
        else:
            out.append(f'"{escape_literal(v)}"{suffix}')
    return out


def reference_lines(tb: Testbed, empty_is_null: bool = True) -> np.ndarray:
    """The KG as sorted distinct ``"<s> <p> <o>"`` lines.  With
    ``empty_is_null=False`` (the control), an empty cell is an empty value:
    an empty literal, an IRI with an empty slot, a join key like any other."""
    subjects = {}
    for name, table, subject, _cls, _poms in MAPS:
        t = tb.tables[table]
        subjects[name] = _template(BASE + subject, t, len(t[COLUMNS[table][0]]), empty_is_null)
    out = set()
    for name, table, _subject, cls, poms in MAPS:
        t = tb.tables[table]
        subj = subjects[name]
        n = len(subj)
        out.update(f"{s} <{RDF_TYPE}> <{GTFS}{cls}>" for s in subj if s is not None)
        for pred, kind, arg, extra in poms:
            if kind == "join":
                child, parent = extra
                ptable = next(m[1] for m in MAPS if m[0] == arg)
                index: dict = {}
                for key, ps in zip(tb.tables[ptable][parent], subjects[arg]):
                    if ps is not None and (key != "" or not empty_is_null):
                        index.setdefault(key, set()).add(ps)
                for s, key in zip(subj, t[child]):
                    if s is not None:
                        out.update(f"{s} <{pred}> {ps}" for ps in index.get(key, ()))
                continue
            objs = (subjects[arg] if kind == "orm"
                    else _objects(kind, arg, extra, t, n, empty_is_null))
            out.update(f"{s} <{pred}> {o}" for s, o in zip(subj, objs)
                       if s is not None and o is not None)
    return np.array(sorted(out))


def null_terms(tb: Testbed) -> int:
    """Source rows that each rule (a class, or one predicate-object map)
    gives no triple for because a term it reads is NULL: an empty cell in
    the subject, the object or the child join column."""
    total = 0
    for _name, table, subject, _cls, poms in MAPS:
        t = tb.tables[table]
        subj_cols = _SLOT.findall(subject)
        null_subj = np.zeros(len(t[COLUMNS[table][0]]), bool)
        for c in subj_cols:
            null_subj |= np.asarray(t[c], dtype=object) == ""
        total += int(null_subj.sum())  # the class rule
        for _pred, kind, arg, extra in poms:
            if kind == "join":
                cols = [extra[0]]
            elif kind == "orm":
                cols = _SLOT.findall(next(m[2] for m in MAPS if m[0] == arg))
            elif kind == "enum":
                cols = _SLOT.findall(arg)
            else:
                cols = [arg]
            null = null_subj.copy()
            for c in cols:
                null |= np.asarray(t[c], dtype=object) == ""
            total += int(null.sum())
    return total
