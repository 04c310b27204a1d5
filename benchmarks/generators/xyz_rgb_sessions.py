"""Generator kind `xyz_rgb_sessions`: `xyz_sessions` over a three-band
(true-colour) layer.

The sessions, the twins and the window are `xyz_sessions.Generator`'s,
with its parameters.  What differs is what a tile is made of and held
to: a layer's `rgb_products` name three namespaces, one per channel,
and the served RGBA PNG is compared with `reference_rgb.py`.  The check's
sample is spread over levels and, within a level, over tiles that touch
one granule and tiles that touch several: the second kind is a mosaic,
and the only place a dropped granule shows.
"""

import io

import numpy as np

from .. import reference, reference_rgb
from . import xyz_sessions
from .xyz_sessions import _unix


class Generator(xyz_sessions.Generator):
    def _channels(self, layer, time):
        return reference_rgb.select_rgb(
            self.sources, self.layers[layer]["rgb_products"], _unix(time))

    def _select(self, layer, time):
        """Every granule of every channel: what the layer shows."""
        return [s for srcs in self._channels(layer, time) for s in srcs]

    def granules_touched(self, layer, time, bbox):
        """How many granules of the layer's first channel the tile's
        footprint overlaps."""
        t = np.linspace(0.0, 1.0, 9)
        ex = np.concatenate([bbox[0] + t * (bbox[2] - bbox[0])] * 2
                            + [np.full(9, bbox[0]), np.full(9, bbox[2])])
        ey = np.concatenate([np.full(9, bbox[1]), np.full(9, bbox[3])]
                            + [bbox[1] + t * (bbox[3] - bbox[1])] * 2)
        n = 0
        for s in self._channels(layer, time)[0]:
            sx, sy = reference.project(ex, ey, "EPSG:3857", s.crs)
            x1, y1 = s.x0 + s.dx * s.shape[1], s.y0 + s.dy * s.shape[0]
            n += bool(sx.max() > min(s.x0, x1) and sx.min() < max(s.x0, x1)
                      and sy.max() > min(s.y0, y1)
                      and sy.min() < max(s.y0, y1))
        return n

    def prefill(self):
        """One tile in the middle of every granule, at the finest level,
        so that every band raster is resident before the first twin: a
        window may then hold no scene load (`demand_still`).  Sent as
        twins, so the window's own tiles stay new to the process."""
        z = self.zooms[-1]
        size = xyz_sessions.WORLD / (1 << z)
        layer, time = next(iter(self.t["layers"])), self.dates[0]
        reqs = []
        for s in self._channels(layer, time)[0]:
            mx, my = reference.project(
                np.array([s.x0 + s.dx * s.shape[1] / 2]),
                np.array([s.y0 + s.dy * s.shape[0] / 2]),
                s.crs, "EPSG:3857")
            reqs.append(self._req(
                layer, z, int((mx[0] + xyz_sessions.WORLD / 2) // size),
                int((xyz_sessions.WORLD / 2 - my[0]) // size), time))
        return self.twins(reqs)

    def _sample(self, results, n):
        """`xyz_sessions`' sample (levels in turn, the larger tiles of
        each), half of it from tiles that touch several granules and
        the rest from tiles that touch one."""
        one, several = [], []
        for r in results:
            if r.ok and r.req.kind == "GetMap":
                m = r.req.meta
                (several if self.granules_touched(
                    m["layer"], m["time"], m["bbox"]) > 1 else one).append(r)
        picked = super()._sample(several, n // 2)
        return super()._sample(one, n - len(picked)) + picked

    def verify(self, results, fetch):
        """(problems, records): a sample of the window's tiles, asked
        for again outside the window (the bytes must be the window's)
        and compared as RGBA with `reference_rgb.py`."""
        from PIL import Image
        problems, records = [], []
        bound = self.t["check"]["bound_mismatch"]
        for seen in self._sample(results, self.t["check"]["tiles"]):
            req = seen.req
            lay = self.layers[req.meta["layer"]]
            rec = {"layer": lay["name"], "z": req.meta["z"],
                   "time": req.meta["time"],
                   "granules": self.granules_touched(
                       lay["name"], req.meta["time"], req.meta["bbox"])}
            records.append(rec)
            res = fetch(req)
            if not res.ok:
                problems.append(f"tile {req.key}: status {res.status}")
                continue
            rec["served_twice"] = res.digest != seen.digest
            if rec["served_twice"]:
                problems.append(f"tile {req.key}: served twice, two answers")
            img = Image.open(io.BytesIO(res.body))
            if img.mode != "RGBA":
                problems.append(f"tile {req.key}: PNG mode {img.mode}, "
                                "not RGBA")
                continue
            got = np.asarray(img)
            want = reference_rgb.render_rgba(
                self._channels(lay["name"], req.meta["time"]),
                req.meta["bbox"], "EPSG:3857", 256, 256,
                lay.get("resample", "near"), lay["offset_value"],
                lay["scale_value"], lay["clip_value"])
            if got.shape != want.shape:
                problems.append(f"tile {req.key}: shape {got.shape}")
                continue
            rec.update(data_fraction=float(np.mean(want[..., 3] != 0)),
                       **reference_rgb.compare(got, want))
            if rec["mismatch"] > bound:
                problems.append(
                    f"tile {req.key}: {rec['mismatch']:.3%} of bytes differ "
                    f"from the reference (bound {bound:.2%})")
        return problems, records
