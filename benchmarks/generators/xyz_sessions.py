"""Generator kind `xyz_sessions`: map sessions on the XYZ tile grid.

A session is what one TerriaJS user does: open a viewport of cols x rows
256-px EPSG:3857 tiles on one layer at one time, then pan by a tile or
two or zoom a level in or out, several times.  A browser keeps the
tiles it has, so a view asks only for the tiles it newly shows.

Parameters (the traffic file):
  loop        {"kind": "closed", "connections": n}: the tiles of all
              sessions in one sequence, taken by n clients that each wait
              for a reply (the only loop the client has)
  layers      {name: share}
  zoom_shares {z: share} of the level a session starts on
  viewport    {"cols": [lo, hi], "rows": [lo, hi]}
  views       [lo, hi] views per session
  step        shares of what each further view does: {"pan": .., "zoom": ..}
  pan_tiles   [lo, hi]
  warmup      see `twins` below and `run.py::warm_up`
  check       {"tiles": n, "bound_mismatch": share of bytes}

No (layer, z, x, y, time) is ever asked for twice in one process, so the
response cache answers nothing.  A session opens anywhere over the
archive's extent, on any of its dates.
"""

import datetime as dt
import io
import math

import numpy as np

from .. import reference
from ..plan import Plan, Req, png_ok

R = 6378137.0
WORLD = 2 * math.pi * R
TWIN_OFFSET_PX = 1.0 / 128      # see `Generator.twins`


def tile_bbox(z, x, y):
    size = WORLD / (1 << z)
    return (-WORLD / 2 + x * size, WORLD / 2 - (y + 1) * size,
            -WORLD / 2 + (x + 1) * size, WORLD / 2 - y * size)


def tile_range(extent, z):
    """(x0, y0, x1, y1) inclusive: the tiles of level z that touch
    `extent` (crs, xmin, ymin, xmax, ymax), itself taken round its edge
    into EPSG:3857."""
    crs, xmin, ymin, xmax, ymax = extent
    t = np.linspace(0.0, 1.0, 33)
    ex = np.concatenate([xmin + t * (xmax - xmin), np.full(33, xmax),
                         xmax - t * (xmax - xmin), np.full(33, xmin)])
    ey = np.concatenate([np.full(33, ymin), ymin + t * (ymax - ymin),
                         np.full(33, ymax), ymax - t * (ymax - ymin)])
    mx, my = reference.project(ex, ey, crs, "EPSG:3857")
    size = WORLD / (1 << z)
    return (int((mx.min() + WORLD / 2) // size),
            int((WORLD / 2 - my.max()) // size),
            int((mx.max() + WORLD / 2) // size),
            int((WORLD / 2 - my.min()) // size))


def _inside(a, n, lo, hi):
    """Start of n tiles kept within lo..hi, or centred on it."""
    room = hi - lo + 1 - n
    return min(max(a, lo), lo + room) if room >= 0 else lo + room // 2


def _between(rng, lo_hi):
    return int(rng.integers(lo_hi[0], lo_hi[1] + 1))


class Generator:
    def __init__(self, traffic, config, archive, seed):
        self.t, self.config, self.seed = traffic, config, seed
        self.archive = archive
        self.extent = archive.extent(config["archive"])
        self.dates = archive.dates(config["archive"])
        self.sources = archive.sources(config["archive"], seed)
        self.layers = {lay["name"]: lay for lay in config["layers"]}
        self.zooms = sorted(int(z) for z in traffic["zoom_shares"])
        self.ranges = {z: tile_range(self.extent, z) for z in self.zooms}
        self._shown = {}
        self.seen = set()

    def _select(self, layer, time):
        lay = self.layers[layer]
        return reference.select(
            self.sources, lay["rgb_products"][0], _unix(time),
            _unix(self.dates[0]) if lay.get("accum") else None)

    def shown(self, layer, ti, z):
        """Tile range of level z over what the layer shows at date ti —
        a user looks where there is something to see."""
        key = (layer, ti, z)
        if key not in self._shown:
            srcs = self._select(layer, self.dates[ti])
            if not srcs:
                self._shown[key] = self.ranges[z]
            else:
                xs = [v for s in srcs
                      for v in (s.x0, s.x0 + s.dx * s.shape[1])]
                ys = [v for s in srcs
                      for v in (s.y0, s.y0 + s.dy * s.shape[0])]
                self._shown[key] = tile_range(
                    (srcs[0].crs, min(xs), min(ys), max(xs), max(ys)), z)
        return self._shown[key]

    # -- one session ----------------------------------------------------------

    def _req(self, layer, z, x, y, time, bbox=None, key=None):
        b = bbox or tile_bbox(z, x, y)
        return Req(
            kind="GetMap", valid=png_ok,
            path=("/ows?service=WMS&request=GetMap&version=1.3.0"
                  f"&layers={layer}&crs=EPSG:3857"
                  f"&bbox={b[0]!r},{b[1]!r},{b[2]!r},{b[3]!r}"
                  f"&width=256&height=256&format=image/png&time={time}"),
            key=key or (layer, z, x, y, time),
            meta={"layer": layer, "z": z, "bbox": b, "time": time})

    def session(self, rng):
        """Yields one list of Reqs per view: the tiles it newly shows."""
        t = self.t
        names = list(t["layers"])
        layer = names[rng.choice(len(names), p=_shares(t["layers"], names))]
        zs = [str(z) for z in self.zooms]
        z = int(zs[rng.choice(len(zs), p=_shares(t["zoom_shares"], zs))])
        cols, rows = _between(rng, t["viewport"]["cols"]), \
            _between(rng, t["viewport"]["rows"])
        ti = int(rng.integers(len(self.dates)))
        x0, y0, x1, y1 = self.ranges[z]
        ax = _between(rng, (x0, x1)) - cols // 2
        ay = _between(rng, (y0, y1)) - rows // 2
        steps = list(t["step"])
        step_p = _shares(t["step"], steps)
        for view in range(_between(rng, t["views"])):
            if view:
                what = steps[rng.choice(len(steps), p=step_p)]
                if what == "pan":
                    d = _between(rng, t["pan_tiles"]) * int(rng.choice((-1, 1)))
                    if rng.random() < 0.5:
                        ax += d
                    else:
                        ay += d
                else:
                    up = rng.random() < 0.5
                    if up and z < self.zooms[-1]:
                        z, ax, ay = z + 1, 2 * ax + cols // 2, 2 * ay + rows // 2
                    elif not up and z > self.zooms[0]:
                        z, ax, ay = z - 1, (ax - cols // 2) // 2, \
                            (ay - rows // 2) // 2
            # a user does not pan off the data: the viewport stays inside
            # what the layer shows at this date, or round it where that
            # is the smaller of the two
            x0, y0, x1, y1 = self.shown(layer, ti, z)
            ax = _inside(ax, cols, x0, x1)
            ay = _inside(ay, rows, y0, y1)
            reqs = []
            for y in range(ay, ay + rows):
                for x in range(ax, ax + cols):
                    r = self._req(layer, z, x, y, self.dates[ti])
                    if r.key in self.seen:      # the browser has it, or
                        continue                # another session had
                    self.seen.add(r.key)
                    reqs.append(r)
            yield reqs

    # -- the phases -------------------------------------------------------------

    def _flat(self, rng):
        while True:
            n = 0
            for reqs in self.session(rng):
                n += len(reqs)
                yield from reqs
            if not n and self._exhausted():
                return

    def _exhausted(self):
        total = sum((r[2] - r[0] + 1) * (r[3] - r[1] + 1)
                    for z, r in self.ranges.items() if z in self.zooms)
        return len(self.seen) >= total * len(self.dates) * len(self.layers)

    def prefill(self):
        """Nothing has to be resident before the first tile."""
        return []

    def twins(self, requests):
        """For each request one that runs the program it will run and
        shares nothing else with it: the same tile `TWIN_OFFSET_PX` of a
        pixel on (same scenes, same gather window, another response,
        another index query, another control grid).  A program's first
        use in a process stalls a dispatch slot while it compiles or
        loads; the twin takes that stall before the window.

        Which program a tile runs is decided by the scenes its bbox
        touches and by its footprint in source pixels, floored and
        padded to a bucket, so the nearer the twin the surer it runs its
        request's program: half a pixel on (a twin until PR 31) is up to
        2 source pixels on, and now and then another bucket or another
        count of scenes, whose program then compiles inside the window
        of a young checkout (PERF.md section 6 has the seed).  How near
        it may be is set by the program's response cache, which keys a
        bbox by 1/256ths of a pixel (`quantise_bbox`): nearer than that
        the window's own tile is answered from the cache and the cell
        measures nothing.  1/128 is two of those steps."""
        out = []
        for req in requests:
            m = req.meta
            b = m["bbox"]
            d = (b[2] - b[0]) / 256.0 * TWIN_OFFSET_PX
            out.append(self._req(
                m["layer"], m["z"], req.key[2], req.key[3], m["time"],
                bbox=(b[0] + d, b[1] + d, b[2] + d, b[3] + d),
                key=req.key + ("twin",)))
        return out

    def window(self):
        rng = np.random.default_rng([self.seed, 1])
        return Plan(self.t["loop"]["connections"], self._flat(rng))

    # -- the check ---------------------------------------------------------------

    def _sample(self, results, n):
        """Served tiles spread over layers and levels: (layer, z) strata
        in turn, drawn from the seed."""
        rng = np.random.default_rng([self.seed, 2])
        strata = {}
        for r in results:
            if r.ok and r.req.kind == "GetMap":
                strata.setdefault((r.req.meta["layer"], r.req.meta["z"]),
                                  []).append(r)
        order = sorted(strata)
        for k in order:
            # an empty tile is a small PNG and checks little: draw from
            # the larger half of each stratum
            big = sorted(strata[k], key=lambda r: r.nbytes)[len(strata[k]) // 2:]
            rng.shuffle(big)
            strata[k] = big
        out = []
        while len(out) < n and any(strata.values()):
            for k in order:
                if strata[k] and len(out) < n:
                    out.append(strata[k].pop())
        return out

    def verify(self, results, fetch):
        """(problems, records): a sample of the window's tiles, asked
        for again outside the window and compared with the reference;
        what comes back (from the response cache or not) must be the
        bytes the window got."""
        from PIL import Image
        problems, records = [], []
        bound = self.t["check"]["bound_mismatch"]
        for seen in self._sample(results, self.t["check"]["tiles"]):
            req = seen.req
            lay = self.layers[req.meta["layer"]]
            rec = {"layer": lay["name"], "z": req.meta["z"],
                   "time": req.meta["time"]}
            records.append(rec)
            res = fetch(req)
            if not res.ok:
                problems.append(f"tile {req.key}: status {res.status}")
                continue
            rec["served_twice"] = res.digest != seen.digest
            if rec["served_twice"]:
                problems.append(f"tile {req.key}: served twice, two answers")
            img = Image.open(io.BytesIO(res.body))
            got = np.asarray(img)
            chosen = self._select(lay["name"], req.meta["time"])
            want = reference.render_tile(
                chosen, req.meta["bbox"], "EPSG:3857", 256, 256,
                lay.get("resample", "near"), lay["offset_value"],
                lay["scale_value"], lay["clip_value"])
            rec.update(scenes=len(chosen),
                       data_fraction=float(np.mean(want != 255)))
            if got.shape != want.shape:
                problems.append(f"tile {req.key}: shape {got.shape}")
                continue
            both = (got != 255) & (want != 255)
            rec.update(
                mismatch=float(np.mean(got != want)),
                validity_mismatch=float(np.mean((got != 255)
                                                != (want != 255))),
                max_byte_diff=int(np.abs(got[both].astype(int)
                                         - want[both].astype(int)).max())
                if both.any() else 0)
            if rec["mismatch"] > bound:
                problems.append(
                    f"tile {req.key}: {rec['mismatch']:.3%} of bytes differ "
                    f"from the reference (bound {bound:.2%})")
            if lay.get("palette") and not _palette_ok(img, lay["palette"]):
                problems.append(f"tile {req.key}: palette is not the "
                                "configured ramp")
        return problems, records


def _palette_ok(img, palette):
    """The PNG's colour table is the configured ramp to within one
    level, and index 255 (no data) is transparent."""
    if img.mode != "P":
        return False
    ramp = reference.palette_ramp(palette["colours"])
    pal = np.array(img.getpalette("RGB"), float).reshape(-1, 3)
    alpha = img.info.get("transparency")
    clear = alpha == 255 if isinstance(alpha, int) else \
        alpha is not None and len(alpha) == 256 and alpha[255] == 0
    return bool(len(pal) == 256 and clear
                and np.abs(pal[:255] - ramp[:255, :3]).max() <= 1.0)


def _shares(d, keys):
    w = np.array([d[k] for k in keys], np.float64)
    return w / w.sum()


def _unix(iso):
    return dt.datetime.strptime(iso, "%Y-%m-%dT%H:%M:%S.%fZ").replace(
        tzinfo=dt.timezone.utc).timestamp()
