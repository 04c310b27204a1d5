"""Generator kind `wcs_exports`: bulk coverage exports over WCS.

A request is what an analyst or a batch client of a GSKY WCS endpoint
sends to pull an area of one scene into a GIS or a notebook: a WCS 1.0.0
GetCoverage of one layer at one TIME, as a float32 GeoTIFF of `size`
pixels in `crs`, at or near the scene's native resolution.

Parameters (the traffic file):
  loop            {"kind": "closed", "connections": n}
  layer           the coverage asked for (not `accum`: TIME selects that
                  date's scene alone)
  crs             of the export ("EPSG:4326")
  size            [width, height] of every timed export
  src_px_per_px   [lo, hi]: the output pixel's size in source pixels,
                  drawn log-uniformly, the same on both axes in metres
  on_scene_min    least share of the export's footprint on the scene of
                  its TIME; the centre is drawn uniformly among those
                  that leave it, so some exports reach over the scene's
                  edge and into its nodata corner
  warmup          `run.py::warm_up`: prefill one export a date, twins in
                  passes
  check           {"exports": n re-fetched whole, "tol_dn",
                  "bound_mismatch", "full": {"size", "blocks", "block",
                  "seam_px"} the one export of the published size sent
                  after the window}

No (TIME, bbox) is asked twice in a process, so the response cache
answers none.
"""

import math
import os
import threading

import numpy as np

from .. import reference, reference_export
from ..plan import Plan, Req

TWIN_OFFSET_PX = 1.0 / 128      # `xyz_sessions.Generator.twins` says why
NODATA = -9999.0                # of every export (`server/ows.py`)
SHARE_GRID = 33                 # points a side that sample a footprint


def tiff_ok(status, body):
    return status == 200 and body[:4] in (b"II*\0", b"MM\0*")


def metres_per_degree(lat):
    """(along a parallel, along a meridian) on the WGS84 ellipsoid."""
    p = math.radians(lat)
    return (111412.84 * math.cos(p) - 93.5 * math.cos(3 * p)
            + 0.118 * math.cos(5 * p),
            111132.92 - 559.82 * math.cos(2 * p) + 1.175 * math.cos(4 * p))


def _epsg(crs):
    return int(crs.rsplit(":", 1)[1])


class Generator:
    def __init__(self, traffic, config, archive, seed):
        """Refuses, before the server starts, a program whose server
        does not make the directory it is told to assemble exports in
        (`gsky_tpu.server.ows.export_temp_dir`): `serve.py` names one
        that is not there, so such a program answers every GeoTIFF
        export with a 500 (PR 37's parent: PERF.md section 6) and the
        run would measure how fast it fails."""
        from gsky_tpu.server import ows
        if not hasattr(ows, "export_temp_dir"):
            raise SystemExit(
                "benchmark: this program's server does not make its "
                "-temp_dir (no gsky_tpu.server.ows.export_temp_dir), so "
                "every GeoTIFF export it assembles there fails with a "
                "500; the cell is not run on it")
        self.t, self.config, self.seed = traffic, config, seed
        p = config["archive"]
        self.dates = archive.dates(p)
        self.sources = archive.sources(p, seed)
        self.corner = p.get("nodata_corner", 0.0)
        self.layer = next(lay for lay in config["layers"]
                          if lay["name"] == traffic["layer"])
        self.tile = (self.layer.get("wcs_max_tile_width", 1024),
                     self.layer.get("wcs_max_tile_height", 1024))
        self.seen = set()

    # -- one export -------------------------------------------------------------

    def _shares(self, src, bbox):
        """(share of the bbox on the scene's raster, share in its nodata
        corner), by a grid of points over the bbox."""
        t = (np.arange(SHARE_GRID) + 0.5) / SHARE_GRID
        X, Y = np.meshgrid(bbox[0] + t * (bbox[2] - bbox[0]),
                           bbox[1] + t * (bbox[3] - bbox[1]))
        sx, sy = reference.project(X, Y, self.t["crs"], src.crs)
        col = (sx - src.x0) / src.dx
        row = (sy - src.y0) / src.dy
        H, W = src.shape
        on = (col >= 0) & (col <= W) & (row >= 0) & (row <= H)
        corner = on & (col < W * self.corner) & (row < H * self.corner)
        return float(on.mean()), float(corner.mean())

    def bbox_at(self, src, cx, cy, half):
        """The export's bbox round the point (cx, cy) of the scene's CRS
        that reaches `half` = (x, y) metres to each side."""
        lon, lat = reference.project(np.array([cx]), np.array([cy]),
                                     src.crs, self.t["crs"])
        m_lon, m_lat = metres_per_degree(float(lat[0]))
        return (float(lon[0]) - half[0] / m_lon,
                float(lat[0]) - half[1] / m_lat,
                float(lon[0]) + half[0] / m_lon,
                float(lat[0]) + half[1] / m_lat)

    def draw(self, rng, size, src_px, kind="window", date=None):
        """One export nobody has asked for: a date (or the one given),
        a pixel size and a centre by the traffic file's rules."""
        width, height = size
        while True:
            ti = int(rng.integers(len(self.dates))) if date is None \
                else date
            src = self.sources[ti]
            ratio = math.exp(rng.uniform(math.log(src_px[0]),
                                         math.log(src_px[1])))
            metres = ratio * abs(src.dx)
            H, W = src.shape
            half = (width * metres / 2, height * metres / 2)
            # anywhere the footprint could still touch the scene; the
            # rule below keeps what leaves enough of it there
            cx = rng.uniform(src.x0 - half[0], src.x0 + W * src.dx + half[0])
            cy = rng.uniform(src.y0 + H * src.dy - half[1], src.y0 + half[1])
            bbox = self.bbox_at(src, cx, cy, half)
            on, corner = self._shares(src, bbox)
            key = (self.t["layer"], self.dates[ti], bbox)
            if on >= self.t["on_scene_min"] and key not in self.seen:
                self.seen.add(key)
                return self._req(ti, bbox, size, key + (kind,), dict(
                    src_px_per_px=ratio, on_scene=on, nodata_corner=corner))

    def _req(self, ti, bbox, size, key, more=()):
        b = bbox
        return Req(
            kind="GetCoverage", valid=tiff_ok, key=key,
            path=("/ows?service=WCS&request=GetCoverage&version=1.0.0"
                  f"&coverage={self.t['layer']}&crs={self.t['crs']}"
                  f"&bbox={b[0]!r},{b[1]!r},{b[2]!r},{b[3]!r}"
                  f"&width={size[0]}&height={size[1]}&format=GeoTIFF"
                  f"&time={self.dates[ti]}"),
            meta=dict(more, ti=ti, bbox=bbox, size=tuple(size),
                      time=self.dates[ti]))

    # -- the phases ---------------------------------------------------------------

    def _flat(self, rng):
        while True:
            yield self.draw(rng, self.t["size"], self.t["src_px_per_px"])

    def window(self):
        rng = np.random.default_rng([self.seed, 1])
        return Plan(self.t["loop"]["connections"], self._flat(rng))

    def prefill(self):
        """One export a date, at the native pixel size, so that every
        scene is resident (and stacked) before the first twin."""
        rng = np.random.default_rng([self.seed, 4])
        return [self.draw(rng, self.t["size"], (1.0, 1.0), "prefill", ti)
                for ti in range(len(self.dates))]

    def twins(self, requests):
        """For each request the same export `TWIN_OFFSET_PX` of an output
        pixel on: the same scene, the same four tiles within 1/128 px,
        so the same gather-window buckets and the same programs; another
        response, another index query, other control grids."""
        out = []
        for req in requests:
            m = req.meta
            b, (w, h) = m["bbox"], m["size"]
            dx = (b[2] - b[0]) / w * TWIN_OFFSET_PX
            dy = (b[3] - b[1]) / h * TWIN_OFFSET_PX
            out.append(self._req(
                m["ti"], (b[0] + dx, b[1] + dy, b[2] + dx, b[3] + dy),
                m["size"], req.key + ("twin",)))
        return out

    # -- the check -------------------------------------------------------------------

    def _sample(self, results, n):
        """Exports of the window to fetch again: the one that reaches
        furthest off its scene, the one deepest in a nodata corner, then
        others drawn from the seed."""
        ok = [r for r in results if r.ok and r.req.kind == "GetCoverage"]
        if not ok:
            return []
        rng = np.random.default_rng([self.seed, 2])
        rest = list(ok)
        rng.shuffle(rest)
        out = {}
        for r in [min(ok, key=lambda r: r.req.meta["on_scene"]),
                  max(ok, key=lambda r: r.req.meta["nodata_corner"])] + rest:
            out.setdefault(id(r), r)
        return list(out.values())[:n]

    def seam_sets(self, size, seam_px):
        """Index sets (rows, cols) of every row and column within
        `seam_px` pixels of a seam between two of the server's tiles."""
        w, h = size
        near = np.arange(-seam_px, seam_px)
        rows = np.concatenate([s + near for s in
                               range(self.tile[1], h, self.tile[1])] or [[]])
        cols = np.concatenate([s + near for s in
                               range(self.tile[0], w, self.tile[0])] or [[]])
        sets = []
        if len(rows):
            sets.append((rows.astype(int)[:, None], np.arange(w)[None, :]))
        if len(cols):
            sets.append((np.arange(h)[:, None], cols.astype(int)[None, :]))
        return sets

    def held(self, req, body, sets=None):
        """(problems, record) of one served export against
        `reference_export.py`: the tags against the request, then the
        pixels, all of them or those of the index `sets`."""
        chk = self.t["check"]
        m = req.meta
        src = self.sources[m["ti"]]
        (w, h), bbox = m["size"], m["bbox"]
        rec = {"time": m["time"], "size": list(m["size"]),
               "src_px_per_px": m.get("src_px_per_px"),
               "on_scene": m.get("on_scene"),
               "nodata_corner": m.get("nodata_corner")}
        try:
            planes, tags = reference_export.read_geotiff(body)
        except Exception as e:      # noqa: BLE001 - any malformed file
            return [f"export {req.key}: unreadable GeoTIFF: {e!r}"], rec
        problems = [f"export {req.key}: {p}" for p in
                    reference_export.georeferencing_problems(
                        tags, bbox, w, h, _epsg(self.t["crs"]), NODATA)]
        if planes.shape != (1, h, w):
            return problems + [f"export {req.key}: shape {planes.shape}"], rec
        got = planes[0]
        if sets is None:
            want, valid = reference_export.render(
                src, bbox, self.t["crs"], w, h, self.layer["resample"])
            parts = [(got, want, valid)]
        else:
            parts = []
            for rows, cols in sets:
                X, Y = reference_export.centres(bbox, w, h, rows, cols)
                want, valid = reference_export.resample_at(
                    src, X, Y, self.t["crs"], self.layer["resample"])
                parts.append((got[rows, cols], want, valid))
        rec.update(_fold([reference_export.compare(
            g, NODATA, want, valid, chk["tol_dn"])
            for g, want, valid in parts], [g.size for g, _, _ in parts]))
        if rec["mismatch"] > chk["bound_mismatch"]:
            problems.append(
                f"export {req.key}: {rec['mismatch']:.3%} of "
                f"{rec['pixels_checked']} pixels differ from the "
                f"reference by validity or by more than "
                f"{chk['tol_dn']} DN (bound {chk['bound_mismatch']:.2%})")
        return problems, rec

    def full_export(self):
        """The one export of the published size, at the native pixel
        size, and the pixels it is checked on: seeded blocks and every
        row and column near a tile seam."""
        full = self.t["check"]["full"]
        rng = np.random.default_rng([self.seed, 3])
        req = self.draw(rng, full["size"], (1.0, 1.0), "full")
        w, h = full["size"]
        n = min(full["block"], w, h)
        sets = []
        for _ in range(full["blocks"]):
            r0 = int(rng.integers(0, h - n + 1))
            c0 = int(rng.integers(0, w - n + 1))
            sets.append((np.arange(r0, r0 + n)[:, None],
                         np.arange(c0, c0 + n)[None, :]))
        return req, sets + self.seam_sets((w, h), full["seam_px"])

    def verify(self, results, fetch):
        """(problems, records): the published size first (one export,
        timed, its host memory read), then a sample of the window's
        exports fetched again whole; each held to the reference and to
        its request's georeferencing, a second fetch to the window's
        bytes."""
        problems, records = [], []
        req, sets = self.full_export()
        with _RssPeak() as rss:
            res = fetch(req)
        rec = {"what": "full"}
        if res.ok:
            more, rec = self.held(req, res.body, sets)
            problems += more
            peak, of = rss.read()
            rec.update(what="full", export_4k_s=res.latency_s,
                       body_bytes=res.nbytes, host_rss_peak_bytes=peak,
                       host_rss_peak_of=of,
                       host_rss_before_bytes=rss.before)
        else:
            problems.append(f"export {req.key}: status {res.status} "
                            f"{(res.body or b'')[-200:]!r}")
        records.append(rec)
        for seen in self._sample(results, self.t["check"]["exports"]):
            res = fetch(seen.req)
            if not res.ok:
                problems.append(f"export {seen.req.key}: status "
                                f"{res.status}")
                records.append({"what": "window"})
                continue
            more, rec = self.held(seen.req, res.body)
            rec.update(what="window",
                       served_twice=res.digest != seen.digest)
            if rec["served_twice"]:
                more.append(f"export {seen.req.key}: served twice, "
                            "two answers")
            problems += more
            records.append(rec)
        return problems, records


def _fold(compared, sizes):
    """One record of several `reference_export.compare` results, each
    weighted by its pixels."""
    n = max(sum(sizes), 1)
    return {"mismatch": sum(c["mismatch"] * s
                            for c, s in zip(compared, sizes)) / n,
            "validity_mismatch": sum(c["validity_mismatch"] * s
                                     for c, s in zip(compared, sizes)) / n,
            "max_abs_err": max(c["max_abs_err"] for c in compared),
            "data_fraction": sum(c["data_fraction"] * s
                                 for c, s in zip(compared, sizes)) / n,
            "pixels_checked": int(sum(sizes))}


class _RssPeak:
    """`with _RssPeak() as rss:` — the process's largest resident size
    while the block runs, sampled every 20 ms from /proc/self/statm on a
    thread of its own (`rss.read()` afterwards: (bytes, "export")).
    Where there is no such file: (`ru_maxrss`, "process"), the peak over
    the process's whole life, the archive's making and the window
    included."""

    def __init__(self):
        self.before = self.peak = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    @staticmethod
    def _now():
        try:
            with open("/proc/self/statm") as fp:
                return int(fp.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
        except (OSError, ValueError, IndexError):
            return 0

    def _run(self):
        while not self._stop.is_set():
            self.peak = max(self.peak, self._now())
            self._stop.wait(0.02)

    def __enter__(self):
        self.before = self.peak = self._now()
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self.peak = max(self.peak, self._now())

    def read(self):
        if self.peak:
            return self.peak, "export"
        import resource
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024, \
            "process"
