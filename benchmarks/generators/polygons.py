"""Generator kind `polygons`: WPS Execute drills, every polygon its own.

An analyst draws a paddock, a property or a catchment and asks for its
time series.  Each polygon is star-shaped round a random centre with
`vertices` [lo, hi] corners; the side of the square of equal area is
log-uniform in `side_px` [lo, hi] source pixels, and the whole polygon
lies inside the stack.  All timesteps and all of the process's bands
are asked for.

Parameters (the traffic file): loop {"kind": "closed", "connections"},
process, vertices, side_px, warmup {"lattice_px": [edge, ...], and what
`run.py::warm_up` reads}, check {"rect_px": [edge, ...], "bound_abs": e}.

The drill's programs are shaped by the polygon's bounding window, each
side padded to a bucket, so the prefill is one polygon for every pair
of `lattice_px` edges (one edge to an octave): sixteen requests that
touch every program the window can need, sent until the stacks are
resident.  Nothing of a drill is cached, so the window's requests need
no twins.

The check holds every drill of the window to what its polygon's
footprint holds on this archive (`reference.footprint_holds_data`
against the archive's nodata block): every timestep a row, every band a
field, finite where the footprint holds a valid pixel and empty where
it holds none; within a pixel of the block's edge either passes and the
run counts it.  The check's own drills are rectangles whose edges run
through pixel centres, so that the all-touched burn is unambiguous
(exactly rows r0..r1 and columns c0..c1), and their values are compared
with the reference's means.
"""

import json
import re

import numpy as np

from .. import reference
from ..plan import Plan, Req


def _wps_ok(status, body):
    return status == 200 and b"ExecuteResponse" in body


ROW = re.compile(rb"(\d{4}-\d\d-\d\d)((?:,[-0-9.eE]*)+)")


def parse_rows(body):
    """{date: [value per band, ...]} from the response's CSV block
    ("date,v1,v2,v3" lines; an empty field is a NaN)."""
    return {d.decode(): [float(v) if v else float("nan")
                         for v in vals.decode().split(",")[1:]]
            for d, vals in ROW.findall(body)}


class Generator:
    def __init__(self, traffic, config, archive, seed):
        self.t, self.config, self.seed = traffic, config, seed
        self.archive, self.p = archive, config["archive"]
        self.xs, self.ys = archive.axes(self.p)

    def _req(self, ring, **meta):
        gj = json.dumps({"type": "FeatureCollection", "features": [{
            "type": "Feature", "geometry": {
                "type": "Polygon",
                "coordinates": [[[float(x), float(y)] for x, y in ring]]}}]})
        body = (
            '<?xml version="1.0" encoding="UTF-8"?>'
            '<wps:Execute version="1.0.0" service="WPS"'
            ' xmlns:wps="http://www.opengis.net/wps/1.0.0"'
            ' xmlns:ows="http://www.opengis.net/ows/1.1">'
            f'<ows:Identifier>{self.t["process"]}</ows:Identifier>'
            '<wps:DataInputs><wps:Input>'
            '<ows:Identifier>geometry</ows:Identifier>'
            '<wps:Data><wps:ComplexData mimeType="application/vnd.geo+json">'
            f'{gj}</wps:ComplexData></wps:Data></wps:Input>'
            '</wps:DataInputs></wps:Execute>')
        return Req(kind="Execute", path="/ows?service=WPS&request=Execute",
                   valid=_wps_ok, body=body.encode(), keep=True, meta=meta)

    def _polygon(self, rng):
        lo, hi = self.t["side_px"]
        side = float(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        n = int(rng.integers(self.t["vertices"][0], self.t["vertices"][1] + 1))
        ang = np.sort(rng.uniform(0, 2 * np.pi, n))
        rad = rng.uniform(0.6, 1.0, n)
        px, py = rad * np.cos(ang), rad * np.sin(ang)
        # scale to the drawn area (shoelace), in pixels
        area = 0.5 * abs(np.dot(px, np.roll(py, -1)) - np.dot(py, np.roll(px, -1)))
        k = side / np.sqrt(area)
        return self._placed(rng, px * k, py * k, side_px=side, vertices=n)

    def _placed(self, rng, px, py, **meta):
        """The polygon with corners (px, py) pixels from its middle, put
        somewhere inside the stack."""
        h, w = self.p["hw"]
        res = self.p["res"]
        reach_x = min(np.abs(px).max(), w / 2 - 2)
        reach_y = min(np.abs(py).max(), h / 2 - 2)
        px = np.clip(px, -reach_x, reach_x)
        py = np.clip(py, -reach_y, reach_y)
        cx = rng.uniform(reach_x + 1, w - reach_x - 1)
        cy = rng.uniform(reach_y + 1, h - reach_y - 1)
        lon0, lat0 = self.p["origin"]
        cols, rows = cx + px, cy + py
        ring = [(lon0 + c * res, lat0 - r * res) for c, r in zip(cols, rows)]
        ring.append(ring[0])
        # the window the drill has to read: rows x columns x timesteps x
        # bands (roofline.py's bytes)
        wh = int(np.ceil(py.max() - py.min())) + 1
        ww = int(np.ceil(px.max() - px.min())) + 1
        return self._req(ring, window_px=(wh, ww), corners_px=(cols, rows),
                         **meta)

    def _flat(self, rng):
        while True:
            yield self._polygon(rng)

    def prefill(self):
        rng = np.random.default_rng(0)
        ang = np.linspace(0, 2 * np.pi, 12, endpoint=False)
        return [self._placed(rng, a / 2 * np.cos(ang), b / 2 * np.sin(ang))
                for a in self.t["warmup"]["lattice_px"]
                for b in self.t["warmup"]["lattice_px"]]

    def window(self):
        rng = np.random.default_rng([self.seed, 1])
        return Plan(self.t["loop"]["connections"], self._flat(rng))

    # -- the check ---------------------------------------------------------------

    def _rectangles(self):
        """Rectangles of the configured edges, placed from the seed."""
        rng = np.random.default_rng([self.seed, 2])
        h, w = self.p["hw"]
        out = []
        for edge in self.t["check"]["rect_px"]:
            edge = min(edge, h - 2, w - 2)
            r0 = int(rng.integers(0, h - edge))
            c0 = int(rng.integers(0, w - edge))
            r1, c1 = r0 + edge - 1, c0 + edge - 1
            xs, ys = self.xs, self.ys
            ring = [(xs[c0], ys[r1]), (xs[c1], ys[r1]), (xs[c1], ys[r0]),
                    (xs[c0], ys[r0]), (xs[c0], ys[r1])]
            out.append(self._req(ring, rect=(r0, r1, c0, c1)))
        return out

    def _held(self, body, holds_data):
        """(state, fault) of one answered drill against what its
        footprint holds (True, False, or None where the reference does
        not decide): every timestep a row and every band a field, all
        finite over a valid pixel, all empty over none.  The nodata block
        is the same in every timestep, so one answer never mixes the
        two."""
        try:
            rows = parse_rows(body)
        except ValueError as e:
            return "malformed", f"a field that is no number ({e})"
        bands = len(self.p["variables"])
        if len(rows) != self.p["steps"]:
            return "malformed", f"{len(rows)} rows, want {self.p['steps']}"
        if any(len(v) != bands for v in rows.values()):
            return "malformed", f"a row without {bands} fields"
        finite = np.isfinite(np.array(list(rows.values())))
        if finite.any() and not finite.all():
            return "malformed", (f"{int((~finite).sum())} empty fields among "
                                 f"{finite.size}")
        got = bool(finite.all())
        if holds_data is None:
            return "undecided", None
        if got != holds_data:
            return "malformed", (
                "empty rows over a footprint that holds data" if holds_data
                else "finite rows over a footprint that holds no data")
        return ("finite" if got else "empty_on_nodata"), None

    def verify(self, results, fetch):
        """(problems, records): every answer of the window by its rows,
        the seeded rectangles also by their values against the
        reference's masked means."""
        problems, records = [], []
        block = self.archive.nodata_below(self.p)
        for i, r in enumerate(results):
            if not r.ok:
                continue
            cols, rows = r.req.meta["corners_px"]
            holds = reference.footprint_holds_data(cols, rows, block)
            state, fault = self._held(r.body, holds)
            records.append({"drill": i, "holds_data": holds, "rows": state})
            if fault:
                problems.append(
                    f"window drill {i} ({r.req.meta['side_px']:.0f} px "
                    f"across, columns {cols.min():.1f}..{cols.max():.1f}, "
                    f"rows {rows.min():.1f}..{rows.max():.1f}): {fault}")
        fields = self.archive.fields(self.p, self.seed)
        steps = self.p["steps"]
        bound = self.t["check"]["bound_abs"]
        # one column per band, in the order the process lists them
        names = self.config["processes"][0]["data_sources"][0]["rgb_products"]
        for req in self._rectangles():
            r0, r1, c0, c1 = req.meta["rect"]
            rec = {"rect": [r0, r1, c0, c1]}
            records.append(rec)
            res = fetch(req)
            if not res.ok:
                problems.append(f"drill {rec['rect']}: status {res.status}")
                continue
            mask = reference.burn_rectangle((r1 - r0 + 1, c1 - c0 + 1),
                                            0, r1 - r0, 0, c1 - c0)
            want, count = zip(*(reference.drill_means(
                fields[n].window(np.arange(steps), r0, r1 + 1, c0, c1 + 1),
                mask, float(self.p["nodata"])) for n in names))
            rec["holds_data"] = bool(np.any(count))
            rec["rows"], fault = self._held(res.body, rec["holds_data"])
            if fault:
                problems.append(f"drill {rec['rect']}: {fault}")
                continue
            if not rec["holds_data"]:
                continue
            rows = parse_rows(res.body)
            got = np.array([rows[d] for d in sorted(rows)])     # (T, bands)
            rec["max_abs_err"] = float(np.abs(got - np.stack(want, 1)).max())
            if rec["max_abs_err"] > bound:
                problems.append(f"drill {rec['rect']}: a mean is off by "
                                f"{rec['max_abs_err']:.3g} (bound {bound})")
        return problems, records
