"""MAS — the metadata index, sqlite-backed.

The reference's MAS is Postgres+PostGIS with a schema-per-shard layout and
a `polygons` materialized view carrying per-subdataset geometries + GIST
indexes (`mas/db/schema.sql`, `mas/MAS_Design.md`).  The HTTP contract it
serves (`mas/api/api.go:58-124`, `mas/api/mas.sql:363-709`) is small:

- ``?intersects``: files (and optionally bundled `gdal` metadata records)
  whose footprint intersects a query geometry and time range
- ``?timestamps``: distinct sorted timestamps with a cache token
- ``?extents``: EPSG:3857 envelope + stamp range + variables

This rebuild keeps that exact JSON contract but stores records in sqlite:
bbox + stamp-range columns do the SQL prefilter, and the final polygon
test runs with our own geometry engine (`mas_intersects`'s ST_Intersects
equivalent).  Ingest takes the same `{"filename", "file_type",
"geo_metadata": [...]}` records the crawler emits
(`crawl/extractor/info.go`).
"""

from __future__ import annotations

import contextlib
import datetime as dt
import hashlib
import json
import math
import sqlite3
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..geo import geometry as geom
from ..geo.crs import EPSG3857, EPSG4326, parse_crs
from ..geo.transform import BBox, transform_bbox

ISO = "%Y-%m-%dT%H:%M:%S.000Z"


def parse_time(s: str) -> float:
    """RFC3339-ish -> unix seconds (the formats Go emits/accepts)."""
    s = s.strip()
    for fmt in ("%Y-%m-%dT%H:%M:%S.%fZ", "%Y-%m-%dT%H:%M:%SZ",
                "%Y-%m-%dT%H:%M:%S%z", "%Y-%m-%d %H:%M:%S", "%Y-%m-%d"):
        try:
            d = dt.datetime.strptime(s, fmt)
            if d.tzinfo is None:
                d = d.replace(tzinfo=dt.timezone.utc)
            return d.timestamp()
        except ValueError:
            continue
    raise ValueError(f"cannot parse time {s!r}")


def timestamps_token(result) -> str:
    """The ?timestamps cache token (`mas/api/mas.sql:549-598`): one
    definition shared by the single store and the sharded router so the
    protocols cannot drift."""
    return hashlib.md5(json.dumps(list(result)).encode()).hexdigest()


def fmt_time(t: float) -> str:
    return dt.datetime.fromtimestamp(t, dt.timezone.utc).strftime(ISO)


class GdalRecord(dict):
    """One `gdal` record of an ``?intersects`` answer: to `json.dumps`
    and every consumer the dict it always was, and beside its items
    `unix`, the record's `timestamps` as unix seconds in the same order
    (what `parse_time` gives for each), so the in-process client does
    not parse them again.  A record's nested values and `unix` belong
    to the store's decoded row and are shared by every query that
    returns the row: read-only to all."""

    __slots__ = ("unix",)

    def copy(self) -> "GdalRecord":
        r = GdalRecord(self)
        r.unix = self.unix
        return r


class _Footprint:
    """What the polygon refinement asks of a geometry in EPSG:4326 (a
    dataset row's footprint, or the query's), as float64 arrays made
    once: its bbox; the vertices it puts to the other side (every
    exterior's, each max(1, n // 64)-th); every ring's edges for the
    even-odd ray cast, end to end in one block; every exterior's
    segments, closed, for the crossing test.  `meets` is the
    ST_Intersects stand-in the store has always computed: bboxes
    overlap, and a vertex of either lies in the other or two exterior
    edges cross; a point geometry meets what contains one of its
    points.  The arithmetic is `Geometry.contains_point`'s and the
    parametric segment test's, element for element, so an answer does
    not depend on which of the two made it."""

    __slots__ = ("bbox", "points", "verts", "x", "y", "y2", "dx", "dy",
                 "starts", "polys", "sx", "sy", "sdx", "sdy")

    def __init__(self, g: geom.Geometry):
        self.bbox = g.bbox()
        self.points = np.asarray(g.points, np.float64) \
            if g.kind in ("Point", "MultiPoint") else None
        polys = [poly for poly in g.polys if poly and len(poly[0])]
        self.verts = _stacked(
            [poly[0][:: max(1, len(poly[0]) // 64)] for poly in polys])
        # ray cast: ring k's edges are [starts[k], starts[k + 1]) of the
        # block; a polygon is (its exterior's k, its holes' ks)
        rings, self.polys = [], []
        for poly in polys:
            holes = [r for r in poly[1:] if len(r)]
            self.polys.append(
                (len(rings),
                 list(range(len(rings) + 1, len(rings) + 1 + len(holes)))))
            rings += [poly[0]] + holes
        xy = _stacked(rings)
        nxt = _stacked([np.roll(r, -1, axis=0) for r in rings])
        self.x, self.y, self.y2 = xy[:, 0], xy[:, 1], nxt[:, 1]
        self.dx, self.dy = nxt[:, 0] - self.x, nxt[:, 1] - self.y
        self.starts = np.cumsum([0] + [len(r) for r in rings[:-1]])
        # crossing: the exteriors' segments, each ring closed first
        closed = [r if r[0][0] == r[-1][0] and r[0][1] == r[-1][1]
                  else np.vstack([r, r[:1]])
                  for r in (poly[0] for poly in polys)]
        p = _stacked([r[:-1] for r in closed])
        d = _stacked([r[1:] - r[:-1] for r in closed])
        self.sx, self.sy, self.sdx, self.sdy = \
            p[:, 0], p[:, 1], d[:, 0], d[:, 1]

    def contains(self, pts: np.ndarray) -> np.ndarray:
        """`Geometry.contains_point` of each of pts (K, 2): in a
        polygon's exterior and in none of its holes, even-odd."""
        if not len(pts) or not len(self.x):
            return np.zeros(len(pts), bool)
        px, py = pts[:, 0:1], pts[:, 1:2]
        cond = (self.y > py) != (self.y2 > py)
        xint = self.x + (py - self.y) * self.dx / self.dy
        hit = cond & (px < xint)                        # (K, edges)
        if len(self.starts) == 1:
            return np.bitwise_xor.reduce(hit, axis=1)
        odd = np.bitwise_xor.reduceat(hit, self.starts, axis=1)
        inside = np.zeros(len(pts), bool)
        for ext, holes in self.polys:
            inside |= odd[:, ext] & ~odd[:, holes].any(axis=1)
        return inside

    def _edges_cross(self, other: "_Footprint") -> bool:
        if not len(self.sx) or not len(other.sx):
            return False
        prx, pry = self.sdx[:, None], self.sdy[:, None]
        qsx, qsy = other.sdx[None, :], other.sdy[None, :]
        dx = other.sx[None, :] - self.sx[:, None]
        dy = other.sy[None, :] - self.sy[:, None]
        rxs = prx * qsy - pry * qsx
        tt = (dx * qsy - dy * qsx) / rxs
        uu = (dx * pry - dy * prx) / rxs
        return bool(((rxs != 0) & (tt >= 0) & (tt <= 1)
                     & (uu >= 0) & (uu <= 1)).any())

    def meets(self, q: "_Footprint") -> bool:
        """Does this row's footprint intersect the query's?  Called
        under `np.errstate(divide="ignore", invalid="ignore")`: a
        horizontal edge and a pair of parallel segments divide by zero
        on the way to an answer that masks them out."""
        if not self.bbox.intersects(q.bbox):
            return False
        if q.points is not None:
            return bool(self.contains(q.points).any())
        if self.points is not None:
            return bool(q.contains(self.points).any())
        # the likeliest first: a tile inside a granule
        return bool(self.contains(q.verts).any()
                    or q.contains(self.verts).any()
                    or self._edges_cross(q))


def _stacked(arrays: List[np.ndarray]) -> np.ndarray:
    return np.concatenate(arrays, axis=0) if arrays \
        else np.zeros((0, 2), np.float64)


_NOT_MADE = object()


class _KeptRow:
    """One dataset row as the store keeps it for a generation: the SQL
    row it was made from, and what queries derive from it, each made by
    the first query that needs it: `footprint` by the polygon
    refinement (a `_Footprint`; None for a polygon that does not parse,
    which keeps its row), `record` by a `gdal` answer."""

    __slots__ = ("row", "footprint", "record")

    def __init__(self, row: tuple):
        self.row = row
        self.footprint = _NOT_MADE
        self.record: Optional[GdalRecord] = None


_ALL_ROWS = ("SELECT datasets.*, rt.xmin, rt.xmax, rt.ymin, rt.ymax"
             " FROM datasets LEFT JOIN datasets_rtree AS rt"
             " ON rt.id = datasets.id ORDER BY datasets.id")
# LIKE folds the ASCII letters and no others
_LIKE_FOLD = {c: c + 32 for c in range(ord("A"), ord("Z") + 1)}


class _GenerationRows:
    """Every dataset row of one generation of a store in memory, and the
    columns `intersects` selects on as arrays over the rows, so a query
    picks its candidates by a few vector comparisons and runs no
    statement (at the price of the rows twice in memory, sqlite's and
    these tuples: ~1.6 s and ~170 MB to build at 100,000 rows,
    once a generation).  The predicates are the statement's: the box is the
    R*Tree's own (float32, rounded outwards; NaN for a row the tree
    leaves out, which then meets no box), a NULL stamp is NaN and passes
    no time test, the path test is LIKE's prefix match (it folds the
    ASCII letters).  The order is the statement's plan's: by (namespace,
    id) under a namespace filter, which walks `idx_ds_ns` (every tile's
    and every drill's query), and by id without one.  By id is how a
    table scan answers and how the R*Tree hands out the cells of its one
    node while the store holds up to 51 boxes; past that the tree walks
    its nodes, an order that depends on the history of its splits and
    that no array reproduces: a box query without namespaces then gets
    the same rows by id, and its `limit` cuts there."""

    _PREFIXES_MAX = 64

    def __init__(self, generation: int, joined: List[tuple],
                 i_path: int, i_ns: int, i_stamps: int):
        self.generation = generation
        self.rows = [r[:-4] for r in joined]
        num = np.array([r[-4:] + r[i_stamps:i_stamps + 2] for r in joined],
                       np.float64).reshape(len(joined), 6)
        self.xmin, self.xmax, self.ymin, self.ymax, self.min_stamp, \
            self.max_stamp = num.T.copy()
        # OVERLAPS' second of slack, as the statement adds it
        self._ends, self._begins = self.max_stamp + 1, self.min_stamp - 1
        # names in idx_ds_ns's order, so a code sorts as its name does;
        # the last slot of a query's table answers for NULL (-1)
        self.ns_code = {n: k for k, n in enumerate(sorted(
            {r[i_ns] for r in joined if r[i_ns] is not None}))}
        self.ns = np.array([self.ns_code.get(r[i_ns], -1) for r in joined],
                           np.intp)
        codes: Dict[str, int] = {}
        self._path_of = np.array(
            [codes.setdefault(r[i_path], len(codes)) for r in joined],
            np.intp)
        self._paths = [p.translate(_LIKE_FOLD) for p in codes]
        self._prefixes: Dict[str, np.ndarray] = {}
        self._prefixes_lock = threading.Lock()

    def under(self, gpath: str) -> np.ndarray:
        """Mask of the rows whose path `LIKE gpath%`, made the first
        time the prefix is asked for."""
        mask = self._prefixes.get(gpath)
        if mask is None:
            prefix = gpath.translate(_LIKE_FOLD)
            mask = np.array([p.startswith(prefix) for p in self._paths],
                            bool)[self._path_of]
            with self._prefixes_lock:
                self._prefixes[gpath] = mask
                while len(self._prefixes) > self._PREFIXES_MAX:
                    del self._prefixes[next(iter(self._prefixes))]
        return mask

    def select(self, mask: np.ndarray, qb: Optional[BBox],
               t_a: Optional[float], t_b: Optional[float],
               namespaces: Optional[Sequence[str]]) -> List[tuple]:
        """The rows of `mask` the statement's WHERE lets through, in the
        statement's order."""
        if qb is not None:
            mask = mask & (self.xmax >= qb.xmin) & (self.xmin <= qb.xmax) \
                & (self.ymax >= qb.ymin) & (self.ymin <= qb.ymax)
        if t_a is not None and t_b is None:
            mask = mask & (self.min_stamp <= t_a) & (self.max_stamp >= t_a)
        elif t_a is not None:
            mask = mask & (t_a < self._ends) & (self._begins < t_b)
        if namespaces:
            asked = np.zeros(len(self.ns_code) + 1, bool)
            asked[[self.ns_code[n] for n in namespaces
                   if n in self.ns_code]] = True
            picked = np.flatnonzero(mask & asked[self.ns])
            picked = picked[np.argsort(self.ns[picked], kind="stable")]
        else:
            picked = np.flatnonzero(mask)
        rows = self.rows
        return [rows[i] for i in picked.tolist()]


_SCHEMA = """
CREATE TABLE IF NOT EXISTS files(
    path TEXT PRIMARY KEY,
    file_type TEXT,
    meta TEXT
);
CREATE TABLE IF NOT EXISTS datasets(
    id INTEGER PRIMARY KEY,
    path TEXT NOT NULL,
    ds_name TEXT,
    namespace TEXT,
    array_type TEXT,
    srs TEXT,
    geo_transform TEXT,
    polygon TEXT,          -- WKT in the file's SRS
    nodata REAL,
    xmin REAL, ymin REAL, xmax REAL, ymax REAL,   -- EPSG:4326 bbox
    min_stamp REAL, max_stamp REAL,               -- unix seconds
    timestamps TEXT,       -- JSON array of RFC3339
    axes TEXT,
    means TEXT,
    sample_counts TEXT,
    geo_loc TEXT,
    overviews TEXT
);
CREATE INDEX IF NOT EXISTS idx_ds_path ON datasets(path);
CREATE INDEX IF NOT EXISTS idx_ds_bbox ON datasets(xmin, xmax, ymin, ymax);
CREATE INDEX IF NOT EXISTS idx_ds_ns ON datasets(namespace);
CREATE TABLE IF NOT EXISTS gsky_meta(k TEXT PRIMARY KEY, v INTEGER);
INSERT OR IGNORE INTO gsky_meta(k, v) VALUES ('generation', 0);
-- R*Tree over footprint bboxes: the role of the reference's partial
-- GIST indexes (mas.sql:363-425) — intersects queries walk the tree
-- instead of scanning the table (measured: 100k granules, p50 21.5 ms
-- scan -> 1-2 ms tree).  Triggers keep it in lockstep with datasets.
CREATE VIRTUAL TABLE IF NOT EXISTS datasets_rtree
    USING rtree(id, xmin, xmax, ymin, ymax);
CREATE TRIGGER IF NOT EXISTS ds_rtree_ins AFTER INSERT ON datasets
WHEN new.xmin IS NOT NULL BEGIN
    INSERT INTO datasets_rtree VALUES
        (new.id, new.xmin, new.xmax, new.ymin, new.ymax);
END;
CREATE TRIGGER IF NOT EXISTS ds_rtree_del AFTER DELETE ON datasets
BEGIN
    DELETE FROM datasets_rtree WHERE id = old.id;
END;
"""

_RTREE_BACKFILL = """
INSERT INTO datasets_rtree
    SELECT id, xmin, xmax, ymin, ymax FROM datasets
    WHERE xmin IS NOT NULL
      AND id NOT IN (SELECT id FROM datasets_rtree)
"""


class MASStore:
    """The index.  Safe for concurrent reads beside a writer.  A file
    database gives every thread a connection of its own and takes no
    lock.  A store in memory has one connection, and `_lock` serialises
    what runs on it: the writes, the set-up reads (`timestamps`,
    `extents`, `list_files`) and the one read that builds a generation's
    rows; `intersects` itself reads the generation as a number and
    picks its candidates from that generation's arrays
    (`_GenerationRows`), so a query takes no statement and no `_lock`."""

    _QUERY_CACHE_MAX = 1024
    # process-wide totals across store instances, reachable by the
    # metrics layer without a handle on the per-server store; guarded by
    # a CLASS-level lock — per-instance locks don't serialise increments
    # across the many MASStore instances a sharded store fans out to
    total_query_hits = 0
    total_query_misses = 0
    # dataset rows handed out by gdal queries: decoded earlier under
    # this generation (hit) or decoded for this query (miss)
    total_row_hits = 0
    total_row_misses = 0
    # candidate rows the polygon refinement tested: footprint prepared
    # earlier under this generation (hit) or prepared for this query
    total_footprint_hits = 0
    total_footprint_misses = 0
    # SQL statements run by the queries the answer cache missed (the
    # read of the generation among them)
    total_sql_statements = 0
    _totals_lock = threading.Lock()
    # rows kept per store, oldest out first.  A row of 1,000 stamps is
    # ~0.15 MB decoded; a tile archive's rows hold one stamp; a
    # footprint is a few hundred bytes a ring
    _ROW_CACHE_MAX = 2048

    def __init__(self, db_path: str = ":memory:"):
        self._db_path = db_path
        from collections import OrderedDict
        self._query_cache: "OrderedDict" = OrderedDict()
        self._cache_lock = threading.Lock()
        self.query_hits = 0
        self.query_misses = 0
        # (generation, {row id: _KeptRow}): what queries derive from a
        # row (its prepared footprint, its gdal record), each made once
        # per generation.  Swapped whole when the generation moves; read
        # without a lock
        self._rows: Tuple[int, Dict[int, _KeptRow]] = (-1, {})
        self.row_hits = 0
        self.row_misses = 0
        self.footprint_hits = 0
        self.footprint_misses = 0
        self.sql_statements = 0
        self._local = threading.local()
        self._memory_conn: Optional[sqlite3.Connection] = None
        # a single :memory: connection is shared across threads, so every
        # statement on it must serialise through _lock: the ingests and
        # the reads named in the class's docstring.  Not a query: a
        # thread back from `execute` waits for the GIL before it can let
        # the lock go, so a lock every query takes is a convoy.  File
        # databases get one connection per thread instead and no lock
        self._lock = threading.Lock()
        # a store in memory is written by this process alone, so its
        # generation is the number of ingests committed here: kept
        # beside gsky_meta's, moved under _lock, read without it
        self._generation = 0
        self._held: Optional[_GenerationRows] = None
        if db_path == ":memory:":
            self._memory_conn = sqlite3.connect(":memory:",
                                                check_same_thread=False)
        with self._maybe_lock():
            self._conn().executescript(_SCHEMA)
            # pre-R*Tree databases: index their existing rows once
            self._conn().execute(_RTREE_BACKFILL)
            self._conn().commit()
        self._columns = [d[0] for d in self._conn().execute(
            "SELECT * FROM datasets LIMIT 0").description]
        self._i_id, self._i_path, self._i_srs, self._i_polygon = (
            self._columns.index(c)
            for c in ("id", "path", "srs", "polygon"))
        self._i_ns, self._i_stamps = (
            self._columns.index(c) for c in ("namespace", "min_stamp"))
        # bumped on every ingest; response caches key on it so cached
        # answers die with the data they were computed from.  Persisted
        # in sqlite (gsky_meta) so an ingest from ANOTHER process against
        # the same file DB (e.g. the crawler CLI) also invalidates this
        # server's cache.

    @property
    def generation(self) -> int:
        if self._memory_conn is not None:
            return self._generation
        row = self._conn().execute(
            "SELECT v FROM gsky_meta WHERE k = 'generation'").fetchone()
        return int(row[0]) if row else 0

    def _maybe_lock(self):
        return self._lock if self._memory_conn is not None \
            else contextlib.nullcontext()

    def _fetchall(self, sql: str, args=()) -> List[tuple]:
        with self._maybe_lock():
            return self._conn().execute(sql, args).fetchall()

    def _conn(self) -> sqlite3.Connection:
        if self._memory_conn is not None:
            return self._memory_conn
        c = getattr(self._local, "conn", None)
        if c is None:
            c = sqlite3.connect(self._db_path)
            self._local.conn = c
        return c

    # -- ingest --------------------------------------------------------------

    def ingest(self, record: Dict) -> int:
        """Ingest one crawler record: {"filename", "file_type",
        "geo_metadata": [...]}.  Returns number of datasets indexed.
        (The bash ingest pipeline `mas/db/shard_ingest.sh` analogue is a
        loop over these.)"""
        return self.ingest_many([record])

    def ingest_many(self, records) -> int:
        """Batch ingest under ONE transaction + one generation bump —
        the crawl pipeline's bulk path (`mas/db/shard_ingest.sh` feeds
        psql a stream the same way).  ~50x faster than per-record
        ingest for catalog-scale loads."""
        n = 0
        with self._maybe_lock():
            conn = self._conn()
            try:
                conn.execute(
                    "UPDATE gsky_meta SET v = v + 1 WHERE k = 'generation'")
                for record in records:
                    path = record.get("filename") or record.get("file_path")
                    if not path:
                        raise ValueError("record missing filename")
                    n += self._ingest_locked(conn, record, path)
                conn.commit()
            except BaseException:
                # a half-ingested record must not linger in the open
                # implicit transaction, where the next successful ingest
                # would commit it
                conn.rollback()
                raise
            if self._memory_conn is not None:
                # with the commit and not with the UPDATE: a query never
                # reads a generation that may yet be rolled back
                self._generation += 1
        return n

    def _ingest_locked(self, conn: sqlite3.Connection, record: Dict,
                       path: str) -> int:
        conn.execute("INSERT OR REPLACE INTO files(path, file_type, meta) "
                     "VALUES (?,?,?)",
                     (path, record.get("file_type", ""), json.dumps(record)))
        conn.execute("DELETE FROM datasets WHERE path = ?", (path,))
        n = 0
        for ds in record.get("geo_metadata", []):
            srs = ds.get("proj_wkt") or ds.get("proj4") or ds.get("srs") or ""
            poly_wkt = ds.get("polygon", "")
            bbox4326 = (None, None, None, None)
            if poly_wkt:
                try:
                    g = geom.from_wkt(poly_wkt)
                    if srs:
                        crs = parse_crs(srs)
                        if crs != EPSG4326:
                            g = g.transform(
                                lambda x, y: crs.transform_to(
                                    EPSG4326, x, y))
                    # dateline-crossing footprints index under the bbox
                    # of their SPLIT parts (reaching +/-180 on each
                    # side), so the prefilter admits queries near the
                    # antimeridian on either side
                    b = g.split_dateline().bbox()
                    bbox4326 = (b.xmin, b.ymin, b.xmax, b.ymax)
                except (ValueError, KeyError):
                    pass
            stamps = ds.get("timestamps") or []
            unix = sorted(parse_time(s) for s in stamps) if stamps else []
            conn.execute(
                "INSERT INTO datasets(path, ds_name, namespace, array_type,"
                " srs, geo_transform, polygon, nodata, xmin, ymin, xmax,"
                " ymax, min_stamp, max_stamp, timestamps, axes, means,"
                " sample_counts, geo_loc, overviews)"
                " VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?,?)",
                (path,
                 ds.get("ds_name", path),
                 _sanitize_ns(ds.get("namespace", "")),
                 ds.get("array_type", "Float32"),
                 srs,
                 json.dumps(ds.get("geotransform") or ds.get("geo_transform")),
                 poly_wkt,
                 _float_or_none(ds.get("nodata")),
                 *bbox4326,
                 unix[0] if unix else None,
                 unix[-1] if unix else None,
                 json.dumps([fmt_time(t) for t in unix]),
                 json.dumps(ds.get("axes")) if ds.get("axes") else None,
                 json.dumps(ds.get("means")) if ds.get("means") else None,
                 json.dumps(ds.get("sample_counts"))
                 if ds.get("sample_counts") else None,
                 json.dumps(ds.get("geo_loc")) if ds.get("geo_loc") else None,
                 json.dumps(ds.get("overviews"))
                 if ds.get("overviews") else None))
            n += 1
        return n

    # -- queries -------------------------------------------------------------

    def intersects(self, gpath: str, srs: str = "", wkt: str = "",
                   nseg: int = 2, time: str = "", until: str = "",
                   namespaces: Optional[Sequence[str]] = None,
                   metadata: str = "", limit: int = 0) -> Dict:
        """`mas_intersects` (`mas/api/mas.sql:363-547`).  Returns
        {"files": [...]} or {"gdal": [...]} when metadata == "gdal".

        What a query derives from a row is made once per generation and
        kept with the row (`_KeptRow`): its footprint parsed,
        reprojected to EPSG:4326, split at the dateline and laid out as
        arrays (`_refine`), its record decoded (`_records`).  A query
        that finds its candidate rows prepared parses and reprojects
        its own geometry only, and tests each row with a few array
        operations (~20 us a row, 50 where no vertex decides and the
        edges are crossed; the first query to meet a UTM row pays ~0.2 ms
        for it).  Results cache per (args, generation) — the
        in-process stand-in for the reference's memcached tier in front
        of MAS (`mas/api/api.go:43-52`): a tile server asks the same
        question for every zoom-level repeat.  Any ingest bumps the
        generation (even from another process against the same file
        DB), so cached answers and kept rows die with the data they
        were computed from.  A query reads the generation once; on a
        store in memory that is a number, and the candidate rows come
        from that generation's arrays (`_candidates`)."""
        generation = self.generation
        ckey = (gpath, srs, wkt, nseg, time, until,
                tuple(namespaces) if namespaces else None, metadata,
                limit, generation)
        with self._cache_lock:
            hit = self._query_cache.get(ckey)
            if hit is not None:
                self.query_hits += 1
                with MASStore._totals_lock:
                    MASStore.total_query_hits += 1
                self._query_cache.move_to_end(ckey)
            else:
                self.query_misses += 1
                with MASStore._totals_lock:
                    MASStore.total_query_misses += 1
        if hit is not None:
            # shallow-per-record copy on hit: callers sort the files
            # list and annotate top-level record dicts, so those copy;
            # inner lists (timestamps, axes) are treated read-only by
            # every consumer — a deepcopy here would cost as much as
            # the query it saves for deep time-series responses
            if "gdal" in hit:
                return {"gdal": [r.copy() for r in hit["gdal"]]}
            return {"files": list(hit["files"])}
        query = None
        if wkt:
            g = geom.from_wkt(wkt)
            if srs:
                crs = parse_crs(srs)
                if crs != EPSG4326:
                    if nseg and nseg > 1:
                        b = g.bbox()
                        seg = max((b.width + b.height) / (2 * nseg), 1e-9)
                        g = g.segmentize(seg)
                    g = g.transform(
                        lambda x, y: crs.transform_to(EPSG4326, x, y))
            # antimeridian-crossing queries split into hemisphere parts
            # (ST_SplitDatelineWGS84, mas.sql:13-84)
            query = _Footprint(g.split_dateline())

        t_a = parse_time(time) if time else None
        t_b = parse_time(until) if until else None

        rows = self._candidates(generation, gpath,
                                query.bbox if query is not None else None,
                                t_a, t_b, namespaces)
        if query is not None:
            rows = self._refine(rows, query, generation, limit)
        elif limit:
            rows = rows[:limit]

        if metadata != "gdal":
            return self._cache_put(
                ckey, {"files": sorted({r[self._i_path] for r in rows})})
        return self._cache_put(
            ckey, {"gdal": self._records(rows, generation)})

    def _candidates(self, generation: int, gpath: str, qb: Optional[BBox],
                    t_a: Optional[float], t_b: Optional[float],
                    namespaces: Optional[Sequence[str]]) -> List[tuple]:
        """The dataset rows under `gpath` that pass the box `qb` (None:
        no geometry, no box test), the time test and the namespace
        filter.  The two kinds of store reach them their own way: one in
        memory from its generation's arrays, a file database by the
        statement; the same rows in the same order, and everything
        after this step is shared."""
        if self._memory_conn is not None:
            held = self._generation_rows(generation)
            return held.select(held.under(gpath), qb, t_a, t_b, namespaces)
        self._count_statements(2)       # the generation's, and this one
        return self._select(gpath, qb, t_a, t_b, namespaces)

    def _select(self, gpath: str, qb: Optional[BBox],
                t_a: Optional[float], t_b: Optional[float],
                namespaces: Optional[Sequence[str]]) -> List[tuple]:
        """`_candidates` by the SQL statement."""
        if qb is not None:
            # R*Tree walk instead of a table scan (GIST-index role);
            # NULL-bbox rows are absent from the tree, matching the old
            # prefilter's `xmin IS NULL` exclusion
            sql = ("SELECT datasets.* FROM datasets"
                   " JOIN datasets_rtree AS rt ON datasets.id = rt.id"
                   " WHERE datasets.path LIKE ? ESCAPE '\\'"
                   " AND rt.xmax >= ? AND rt.xmin <= ?"
                   " AND rt.ymax >= ? AND rt.ymin <= ?")
            args: List = [_like_prefix(gpath),
                          qb.xmin, qb.xmax, qb.ymin, qb.ymax]
        else:
            sql = "SELECT * FROM datasets WHERE path LIKE ? ESCAPE '\\'"
            args = [_like_prefix(gpath)]
        if t_a is not None and t_b is None:
            sql += " AND min_stamp <= ? AND max_stamp >= ?"
            args += [t_a, t_a]
        elif t_a is not None and t_b is not None:
            # postgres OVERLAPS with the reference's 1s slack
            sql += " AND ? < max_stamp + 1 AND min_stamp - 1 < ?"
            args += [t_a, t_b]
        if namespaces:
            sql += " AND namespace IN (%s)" % ",".join("?" * len(namespaces))
            args += list(namespaces)
        return self._fetchall(sql, args)

    def _generation_rows(self, generation: int) -> _GenerationRows:
        """The rows a store in memory holds for `generation`, read by
        one statement under the lock the first time a query meets the
        generation.  A query that read its generation before an ingest
        nobody has built for yet is given the newer rows, as the
        statement would have given it."""
        held = self._held
        if held is None or held.generation < generation:
            with self._lock:
                held = self._held
                if held is None or held.generation != self._generation:
                    held = self._held = _GenerationRows(
                        self._generation,
                        self._memory_conn.execute(_ALL_ROWS).fetchall(),
                        self._i_path, self._i_ns, self._i_stamps)
                    self._count_statements(1)
        return held

    def _count_statements(self, n: int) -> None:
        with MASStore._totals_lock:
            self.sql_statements += n
            MASStore.total_sql_statements += n

    def _refine(self, rows: List[tuple], query: _Footprint,
                generation: int, limit: int) -> List[tuple]:
        """The rows whose footprint intersects the query's exactly, in
        EPSG:4326, in the order given, cut at `limit`.  A row's
        footprint is prepared once per generation and kept with the row
        (`_kept_row`); a row with no polygon, or with one that does not
        parse, stays in."""
        kept = self._kept_rows(generation)
        i_polygon = self._i_polygon
        out = []
        tested = hits = 0
        with np.errstate(divide="ignore", invalid="ignore"):
            for row in rows:
                if row[i_polygon]:
                    tested += 1
                    entry = self._kept_row(kept, row)
                    footprint = entry.footprint
                    if footprint is _NOT_MADE:
                        footprint = entry.footprint = self._prepare(row)
                    else:
                        hits += 1
                    if footprint is not None and not footprint.meets(query):
                        continue
                out.append(row)
                if limit and len(out) >= limit:
                    break
        with MASStore._totals_lock:
            self.footprint_hits += hits
            self.footprint_misses += tested - hits
            MASStore.total_footprint_hits += hits
            MASStore.total_footprint_misses += tested - hits
        return out

    def _prepare(self, row: tuple) -> Optional[_Footprint]:
        try:
            p = geom.from_wkt(row[self._i_polygon])
            if row[self._i_srs]:
                crs = parse_crs(row[self._i_srs])
                if crs != EPSG4326:
                    p = p.transform(
                        lambda x, y: crs.transform_to(EPSG4326, x, y))
            # zone-60/zone-1 footprints: split before testing
            return _Footprint(p.split_dateline())
        except (ValueError, KeyError):
            return None

    def _kept_rows(self, generation: int) -> Dict[int, _KeptRow]:
        """The rows kept for `generation`: the store's own dict, begun
        anew when the generation has moved on; a dict of the query's own
        for one that read its generation before an ingest that others
        have seen since."""
        gen, kept = self._rows
        if gen < generation:
            with self._cache_lock:
                if self._rows[0] < generation:
                    self._rows = (generation, {})
                gen, kept = self._rows
        return kept if gen == generation else {}

    def _kept_row(self, kept: Dict[int, _KeptRow], row: tuple) -> _KeptRow:
        """The kept entry of an SQL row, begun now if there is none.  An
        entry answers only for the row it was made from (same id AND
        equal columns: sqlite hands a deleted row's id to the next
        insert, and a query that read its generation before an ingest
        may select after it)."""
        entry = kept.get(row[self._i_id])
        if entry is None or entry.row != row:
            entry = _KeptRow(row)
            with self._cache_lock:
                kept[row[self._i_id]] = entry
                while len(kept) > self._ROW_CACHE_MAX:
                    del kept[next(iter(kept))]
        return entry

    def _records(self, rows: List[tuple],
                 generation: int) -> List[GdalRecord]:
        """The `gdal` record of each SQL row, each a copy (its own top
        level, shared insides) of the row's decoded record.  A row is
        decoded (its JSON columns loaded, its stamps parsed) once per
        generation and kept with the row (`_kept_row`)."""
        kept = self._kept_rows(generation)
        out = []
        hits = 0
        for row in rows:
            entry = self._kept_row(kept, row)
            rec = entry.record
            if rec is None:
                rec = entry.record = self._decode(row)
            else:
                hits += 1
            out.append(rec.copy())
        with MASStore._totals_lock:
            self.row_hits += hits
            self.row_misses += len(rows) - hits
            MASStore.total_row_hits += hits
            MASStore.total_row_misses += len(rows) - hits
        return out

    def _decode(self, row: tuple) -> GdalRecord:
        r = dict(zip(self._columns, row))
        rec = GdalRecord({
            "file_path": r["path"],
            "ds_name": r["ds_name"],
            "namespace": r["namespace"],
            "array_type": r["array_type"],
            "srs": r["srs"],
            "geo_transform": json.loads(r["geo_transform"] or "null"),
            "timestamps": json.loads(r["timestamps"] or "[]"),
            "polygon": r["polygon"],
            "overviews": json.loads(r["overviews"]) if r["overviews"] else None,
            "means": json.loads(r["means"]) if r["means"] else None,
            "sample_counts": json.loads(r["sample_counts"])
            if r["sample_counts"] else None,
            "nodata": r["nodata"] if r["nodata"] is not None else 0.0,
            "axes": json.loads(r["axes"]) if r["axes"] else None,
            "geo_loc": json.loads(r["geo_loc"]) if r["geo_loc"] else None,
        })
        rec.unix = [parse_time(s) for s in rec["timestamps"]]
        return rec

    def _cache_put(self, ckey, value: Dict) -> Dict:
        # NOTE: this, api.MasQueryCache and executor's geo cache are
        # three small LRUs with different value lifetimes (raw query
        # dicts / HTTP byte bodies / numpy+device arrays); kept separate
        # deliberately — a shared helper would couple their eviction
        # policies for ~10 lines of savings each
        if "gdal" in value:
            kept = {"gdal": [r.copy() for r in value["gdal"]]}
        else:
            kept = {"files": list(value["files"])}
        with self._cache_lock:
            self._query_cache[ckey] = kept
            while len(self._query_cache) > self._QUERY_CACHE_MAX:
                self._query_cache.popitem(last=False)
        return value

    def timestamps(self, gpath: str, time: str = "", until: str = "",
                   namespaces: Optional[Sequence[str]] = None,
                   token: str = "") -> Dict:
        """`mas_timestamps` with the cache-token protocol
        (`mas/api/mas.sql:549-598`): a matching token short-circuits to an
        empty list (caller keeps its cache)."""
        t_a = parse_time(time) if time else None
        t_b = parse_time(until) if until else dt.datetime.now(
            dt.timezone.utc).timestamp()
        sql = ("SELECT timestamps FROM datasets WHERE path LIKE ? "
               "ESCAPE '\\'")
        args: List = [_like_prefix(gpath)]
        if namespaces:
            sql += " AND namespace IN (%s)" % ",".join("?" * len(namespaces))
            args += list(namespaces)
        stamps = set()
        for (ts_json,) in self._fetchall(sql, args):
            for s in json.loads(ts_json or "[]"):
                t = parse_time(s)
                if (t_a is None or t >= t_a) and t <= t_b:
                    stamps.add(t)
        result = [fmt_time(t) for t in sorted(stamps)]
        query_token = timestamps_token(result)
        if token and token == query_token:
            return {"timestamps": [], "token": token}
        return {"timestamps": result, "token": query_token}

    def extents(self, gpath: str,
                namespaces: Optional[Sequence[str]] = None) -> Dict:
        """`mas_spatial_temporal_extents` (`mas/api/mas.sql:640-709`):
        EPSG:3857 envelope + stamp range + variable list."""
        sql = ("SELECT namespace, xmin, ymin, xmax, ymax, min_stamp,"
               " max_stamp FROM datasets WHERE path LIKE ? ESCAPE '\\'")
        args: List = [_like_prefix(gpath)]
        if namespaces:
            sql += " AND namespace IN (%s)" % ",".join("?" * len(namespaces))
            args += list(namespaces)
        rows = self._fetchall(sql, args)
        if not rows:
            return {}
        nss = sorted({r[0] for r in rows if r[0]})
        xs0 = [r[1] for r in rows if r[1] is not None]
        ys0 = [r[2] for r in rows if r[2] is not None]
        xs1 = [r[3] for r in rows if r[3] is not None]
        ys1 = [r[4] for r in rows if r[4] is not None]
        stamps_min = [r[5] for r in rows if r[5] is not None]
        stamps_max = [r[6] for r in rows if r[6] is not None]
        out: Dict = {"variables": nss}
        if xs0:
            b = transform_bbox(BBox(min(xs0), min(ys0), max(xs1), max(ys1)),
                               EPSG4326, EPSG3857)
            out.update({"xmin": b.xmin, "ymin": b.ymin,
                        "xmax": b.xmax, "ymax": b.ymax})
        if stamps_min:
            out["min_stamp"] = fmt_time(min(stamps_min))
            out["max_stamp"] = fmt_time(max(stamps_max))
        return out

    def list_files(self) -> List[str]:
        return [r[0] for r in self._fetchall(
            "SELECT path FROM files ORDER BY path")]


def sanitize_namespace(ns: str) -> str:
    """`regexp_replace(trim(ns), '[^a-zA-Z0-9_]', '_')` (mas.sql:495) —
    the single source of the namespace character rule, shared with the
    crawler."""
    import re
    return re.sub(r"[^a-zA-Z0-9_]", "_", ns.strip())


_sanitize_ns = sanitize_namespace


def _float_or_none(v) -> Optional[float]:
    if v is None:
        return None
    try:
        f = float(v)
        return None if math.isnan(f) else f
    except (TypeError, ValueError):
        return None


def _like_prefix(gpath: str) -> str:
    esc = gpath.replace("\\", "\\\\").replace("%", r"\%").replace("_", r"\_")
    return esc + "%"
