"""Archive kind `netcdf_stack`: a time series of one grid, one NetCDF-3
classic file per variable (a classic file's offsets end at 2 GB).

Parameters (the configuration's "archive" group): variables, steps, hw,
origin [lon, lat] of the outer north-west corner, res (degrees),
first_date, step_days, nodata, nodata_corner, collection.

The stack is written by this module's own streaming writer, chunk by
chunk, because the program's `write_netcdf3` holds four copies of a
1 GB variable and takes 12 s for each (PERF.md, Open questions).
Timestep t of variable v is a closed formula of the seed
(`Field.window`): a smooth field, a pattern whose weight follows the
seasons, and one of eight noise fields, clipped to a cover fraction in
[0, 1].  So the reference makes any slice or window again without
holding 3 GB.
"""

import datetime as dt
import os
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..reference import Source

NOISE_FIELDS = 8
CHUNK = 40          # timesteps drawn and written at a time


def _t0(p):
    return dt.datetime.fromisoformat(p["first_date"]).replace(
        tzinfo=dt.timezone.utc)


def times(p):
    """Unix seconds of every timestep."""
    return _t0(p).timestamp() + np.arange(p["steps"]) \
        * p["step_days"] * 86400.0


def dates(p):
    return [(_t0(p) + dt.timedelta(days=int(t) * p["step_days"]))
            .strftime("%Y-%m-%dT%H:%M:%S.000Z") for t in range(p["steps"])]


def axes(p):
    """Pixel-centre longitudes and latitudes."""
    h, w = p["hw"]
    return (p["origin"][0] + (np.arange(w) + 0.5) * p["res"],
            p["origin"][1] - (np.arange(h) + 0.5) * p["res"])


def extent(p):
    h, w = p["hw"]
    lon, lat = p["origin"]
    return ("EPSG:4326", lon, lat - h * p["res"], lon + w * p["res"], lat)


def nodata_below(p):
    """(rows, columns) of the north-west block that holds no data, in
    every timestep of every variable."""
    h, w = p["hw"]
    return int(h * p["nodata_corner"]), int(w * p["nodata_corner"])


class Field:
    """The seeded parts of one variable; `window(ts, r0, r1, c0, c1)`
    gives timesteps ts of rows r0..r1-1, columns c0..c1-1."""

    def __init__(self, p, seed, v):
        h, w = p["hw"]
        T = p["steps"]
        rng = np.random.default_rng([seed, v])
        ph = rng.uniform(0, 2 * np.pi, 6)
        yy = np.arange(h, dtype=np.float32)
        xx = np.arange(w, dtype=np.float32)
        self.base = (0.45 + 0.25 * np.outer(
            np.cos(yy * np.float32(2 * np.pi / 310) + np.float32(ph[0])),
            np.sin(xx * np.float32(2 * np.pi / 420) + np.float32(ph[1])))
        ).astype(np.float32)
        self.pat = (0.15 * np.outer(
            np.sin(yy * np.float32(2 * np.pi / 120) + np.float32(ph[2])),
            np.cos(xx * np.float32(2 * np.pi / 150) + np.float32(ph[3])))
        ).astype(np.float32)
        # 8-day composites: 46 steps to a year
        t = np.arange(T, dtype=np.float32)
        self.a = (1.0 + 0.1 * np.sin(t * np.float32(2 * np.pi / 46)
                                     + np.float32(ph[4]))).astype(np.float32)
        self.b = np.cos(t * np.float32(2 * np.pi / 46)
                        + np.float32(ph[5])).astype(np.float32)
        self.noise = rng.uniform(-0.03, 0.03, (NOISE_FIELDS, h, w)) \
            .astype(np.float32)
        self.nodata = np.float32(p["nodata"])
        self.edge = nodata_below(p)

    def window(self, ts, r0, r1, c0, c1, out=None, tmp=None):
        """`out` and `tmp`: float32 work buffers of at least this
        shape, for the writer, which would otherwise fault in 100 MB of
        fresh pages for every chunk."""
        ts = np.asarray(ts)
        shape = (len(ts), r1 - r0, c1 - c0)
        out = np.empty(shape, np.float32) if out is None else out[:len(ts)]
        tmp = np.empty(shape, np.float32) if tmp is None else tmp[:len(ts)]
        np.multiply(self.base[None, r0:r1, c0:c1], self.a[ts, None, None],
                    out=out)
        np.multiply(self.pat[None, r0:r1, c0:c1], self.b[ts, None, None],
                    out=tmp)
        out += tmp
        np.take(self.noise[:, r0:r1, c0:c1], ts % NOISE_FIELDS, axis=0,
                out=tmp, mode="clip")
        out += tmp
        np.clip(out, 0.0, 1.0, out=out)
        er, ec = self.edge
        out[:, : max(er - r0, 0), : max(ec - c0, 0)] = self.nodata
        return out


def fields(p, seed):
    return {name: Field(p, seed, v)
            for v, name in enumerate(p["variables"])}


def sources(p, seed):
    """One Source per (variable, timestep) for the tile reference; a
    variable's seeded parts are drawn when one of its slices is first
    read."""
    h, w = p["hw"]
    lon, lat = p["origin"]
    ts = times(p)
    made = {}

    def read(v, name, t):
        if name not in made:
            made[name] = Field(p, seed, v)
        return made[name].window([t], 0, h, 0, w)[0]

    return [Source(namespace=name, timestamp=float(ts[t]), crs="EPSG:4326",
                   x0=lon, y0=lat, dx=p["res"], dy=-p["res"], shape=(h, w),
                   nodata=float(p["nodata"]),
                   read=lambda v=v, name=name, t=t: read(v, name, t))
            for v, name in enumerate(p["variables"])
            for t in range(p["steps"])]


# --- NetCDF-3 classic, streamed ----------------------------------------------

NC_CHAR, NC_INT, NC_FLOAT, NC_DOUBLE = 2, 4, 5, 6
_TYPES = {NC_INT: ">i4", NC_FLOAT: ">f4", NC_DOUBLE: ">f8"}


def _pad(b):
    return b + b"\0" * (-len(b) % 4)


def _name(s):
    return struct.pack(">I", len(s)) + _pad(s.encode())


def _atts(atts):
    if not atts:
        return struct.pack(">II", 0, 0)
    out = struct.pack(">II", 0x0C, len(atts))
    for k, (typ, val) in atts.items():
        raw = val.encode() if typ == NC_CHAR else \
            np.atleast_1d(val).astype(_TYPES[typ]).tobytes()
        n = len(raw) if typ == NC_CHAR else len(np.atleast_1d(val))
        out += _name(k) + struct.pack(">II", typ, n) + _pad(raw)
    return out


def write_stack(path, name, p, chunks):
    """One (time, y, x) float variable with CF axes and a grid mapping;
    `chunks` yields the float32 data in time order."""
    from gsky_tpu.geo.crs import parse_crs      # for the CRS's WKT only
    h, w = p["hw"]
    T = p["steps"]
    xs, ys = axes(p)
    dims = [("time", T), ("y", h), ("x", w)]
    fixed = [
        ("x", (2,), {"standard_name": (NC_CHAR, "longitude"),
                     "units": (NC_CHAR, "degrees_east")}, NC_DOUBLE, xs),
        ("y", (1,), {"standard_name": (NC_CHAR, "latitude"),
                     "units": (NC_CHAR, "degrees_north")}, NC_DOUBLE, ys),
        ("time", (0,), {"standard_name": (NC_CHAR, "time"),
                        "units": (NC_CHAR,
                                  "seconds since 1970-01-01 00:00:00")},
         NC_DOUBLE, times(p)),
        ("crs", (), {"spatial_ref": (
            NC_CHAR, parse_crs("EPSG:4326").to_wkt())}, NC_INT,
         np.zeros(1, np.int32)),
    ]
    var_atts = {"grid_mapping": (NC_CHAR, "crs"),
                "_FillValue": (NC_FLOAT, np.float32(p["nodata"]))}
    head = b"CDF\x01" + struct.pack(">I", 0)
    head += struct.pack(">II", 0x0A, len(dims))
    for dname, n in dims:
        head += _name(dname) + struct.pack(">I", n)
    head += _atts({"Conventions": (NC_CHAR, "CF-1.6")})
    entries = []        # (bytes before `begin`, payload or None, vsize)
    for vname, dimids, atts, typ, arr in fixed:
        raw = _pad(np.asarray(arr).astype(_TYPES[typ]).tobytes())
        ent = _name(vname) + struct.pack(">I", len(dimids)) \
            + b"".join(struct.pack(">I", d) for d in dimids) \
            + _atts(atts) + struct.pack(">II", typ, len(raw))
        entries.append((ent, raw, len(raw)))
    vsize = T * h * w * 4
    if vsize >= 1 << 32:
        raise ValueError("a classic variable holds less than 4 GiB")
    ent = _name(name) + struct.pack(">IIII", 3, 0, 1, 2) + _atts(var_atts) \
        + struct.pack(">II", NC_FLOAT, vsize)
    entries.append((ent, None, vsize))
    head += struct.pack(">II", 0x0B, len(entries))
    begin = len(head) + sum(len(e) + 4 for e, _, _ in entries)
    table = b""
    for ent, _, size in entries:
        table += ent + struct.pack(">I", begin)
        begin += size
    with open(path, "wb") as fp:
        fp.write(head + table)
        for _, raw, _ in entries[:-1]:
            fp.write(raw)
        swapped = None
        for block in chunks:
            if swapped is None:
                swapped = np.empty(block.shape, ">f4")
            np.copyto(swapped[:len(block)], block)
            fp.write(swapped[:len(block)].data)


def build(p, seed, root):
    """Write root/<collection>/<variable>.nc for every variable, in
    threads, and return the crawl records."""
    from gsky_tpu.index.crawler import extract

    coll = os.path.join(root, p["collection"])
    os.makedirs(coll)
    h, w = p["hw"]

    def one(item):
        v, name = item
        f = Field(p, seed, v)
        path = os.path.join(coll, f"{name}.nc")
        out, tmp = np.empty((2, CHUNK, h, w), np.float32)
        write_stack(path, name, p, (
            f.window(np.arange(t, min(t + CHUNK, p["steps"])), 0, h, 0, w,
                     out, tmp)
            for t in range(0, p["steps"], CHUNK)))
        rec = extract(path)
        if rec.get("error"):
            raise RuntimeError(f"crawl failed: {rec}")
        return rec

    with ThreadPoolExecutor(len(p["variables"])) as ex:
        return list(ex.map(one, enumerate(p["variables"])))
