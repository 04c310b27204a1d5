"""Generator kind `xyz_seam_sessions`: `xyz_rgb_sessions` over the strip
where granules of two UTM zones overlap (archive kind
`sentinel2_zone_seam`), so that a tile reads granules in two CRSs.

The tiles, the twins, the prefill (one tile a granule) and the check
against `reference_rgb.py` are `xyz_rgb_sessions`', with its parameters.
What differs:

- where a session looks: a view's centre is drawn uniformly over the
  tiles of its level that the strip covers (the archive's `extent`), and
  a pan or a zoom that would carry the centre past the strip's edge is
  reflected back into it;
- what the check samples: of `check.tiles` tiles, three in four (6 of
  8) from those whose footprint touches granules of both zones, the
  rest from the others; a window that has too few of the first kind is
  not `correct`.

Refuses, before the server starts, a program that forms no band set in
several CRSs: there every tile of this cell takes the modular route,
whose multi-CRS leg asks the scene cache for a stacked copy of each
zone's scenes, 1-4 GB a zone beside 5.8 GB of resident rasters.
"""

import importlib

import numpy as np

from . import xyz_rgb_sessions, xyz_sessions
from .xyz_sessions import _between, _shares


def _reflect(c, lo, hi):
    """Tile index c reflected at lo and hi into lo..hi."""
    span = hi - lo
    if span <= 0:
        return lo
    c = (c - lo) % (2 * span)
    return lo + (c if c <= span else 2 * span - c)


class Generator(xyz_rgb_sessions.Generator):
    def __init__(self, traffic, config, archive, seed):
        executor = importlib.import_module("gsky_tpu.pipeline.executor")
        if not hasattr(executor, "_set_crs"):
            raise SystemExit(
                "benchmark: this program forms no band set in several "
                "CRSs (gsky_tpu.pipeline.executor._set_crs); every tile "
                "of this cell would take its modular route and stack each "
                "zone's rasters, so the cell is not run on it")
        super().__init__(traffic, config, archive, seed)
        self._touched = {}

    def shown(self, layer, ti, z):
        """The strip's tiles of level z: where the sessions look."""
        return self.ranges[z]

    def session(self, rng):
        """Yields one list of Reqs per view: the tiles it newly shows.
        `xyz_sessions.Generator.session`'s views, steps and viewport,
        kept by its centre inside the strip."""
        t = self.t
        names = list(t["layers"])
        layer = names[rng.choice(len(names), p=_shares(t["layers"], names))]
        zs = [str(z) for z in self.zooms]
        z = int(zs[rng.choice(len(zs), p=_shares(t["zoom_shares"], zs))])
        cols, rows = _between(rng, t["viewport"]["cols"]), \
            _between(rng, t["viewport"]["rows"])
        ti = int(rng.integers(len(self.dates)))
        x0, y0, x1, y1 = self.ranges[z]
        cx, cy = _between(rng, (x0, x1)), _between(rng, (y0, y1))
        steps = list(t["step"])
        step_p = _shares(t["step"], steps)
        for view in range(_between(rng, t["views"])):
            if view:
                what = steps[rng.choice(len(steps), p=step_p)]
                if what == "pan":
                    d = _between(rng, t["pan_tiles"]) * int(rng.choice((-1, 1)))
                    if rng.random() < 0.5:
                        cx += d
                    else:
                        cy += d
                else:
                    up = rng.random() < 0.5
                    if up and z < self.zooms[-1]:
                        z, cx, cy = z + 1, 2 * cx, 2 * cy
                    elif not up and z > self.zooms[0]:
                        z, cx, cy = z - 1, cx // 2, cy // 2
            x0, y0, x1, y1 = self.ranges[z]
            cx, cy = _reflect(cx, x0, x1), _reflect(cy, y0, y1)
            reqs = []
            for y in range(cy - rows // 2, cy - rows // 2 + rows):
                for x in range(cx - cols // 2, cx - cols // 2 + cols):
                    r = self._req(layer, z, x, y, self.dates[ti])
                    if r.key in self.seen:
                        continue
                    self.seen.add(r.key)
                    reqs.append(r)
            yield reqs

    def zones_touched(self, layer, time, bbox):
        """How many zones' granules (of the layer's first channel) the
        tile's footprint overlaps."""
        key = (layer, time, bbox)
        if key not in self._touched:
            self._touched[key] = len({
                s.crs for s in self._channels(layer, time)[0]
                if self._overlaps(s, bbox)})
        return self._touched[key]

    def _overlaps(self, s, bbox):
        t = np.linspace(0.0, 1.0, 9)
        ex = np.concatenate([bbox[0] + t * (bbox[2] - bbox[0])] * 2
                            + [np.full(9, bbox[0]), np.full(9, bbox[2])])
        ey = np.concatenate([np.full(9, bbox[1]), np.full(9, bbox[3])]
                            + [bbox[1] + t * (bbox[3] - bbox[1])] * 2)
        from .. import reference
        sx, sy = reference.project(ex, ey, "EPSG:3857", s.crs)
        x1, y1 = s.x0 + s.dx * s.shape[1], s.y0 + s.dy * s.shape[0]
        return bool(sx.max() > min(s.x0, x1) and sx.min() < max(s.x0, x1)
                    and sy.max() > min(s.y0, y1)
                    and sy.min() < max(s.y0, y1))

    def _both(self, r):
        m = r.req.meta
        return self.zones_touched(m["layer"], m["time"], m["bbox"]) > 1

    def _sample(self, results, n):
        """`xyz_sessions`' sample (levels in turn, the larger tiles of
        each), three in four of it from tiles over both zones."""
        both, one = [], []
        for r in results:
            if r.ok and r.req.kind == "GetMap":
                (both if self._both(r) else one).append(r)
        picked = xyz_sessions.Generator._sample(
            self, both, n - n // 4)
        return xyz_sessions.Generator._sample(
            self, one, n - len(picked)) + picked

    def verify(self, results, fetch):
        """(problems, records): `xyz_rgb_sessions`' check of the sample,
        each record with the zones its tile touches; too few tiles over
        both zones is a problem of its own."""
        problems, records = super().verify(results, fetch)
        sample = self._sample(results, self.t["check"]["tiles"])
        for rec, r in zip(records, sample):
            rec["zones"] = self.zones_touched(
                r.req.meta["layer"], r.req.meta["time"], r.req.meta["bbox"])
        want = self.t["check"]["tiles"] - self.t["check"]["tiles"] // 4
        got = sum(rec.get("zones", 0) > 1 for rec in records)
        if got < want:
            problems.append(f"{got} of the checked tiles span both zones "
                            f"(the check wants {want})")
        return problems, records
