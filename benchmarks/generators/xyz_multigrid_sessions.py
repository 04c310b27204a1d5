"""Generator kind `xyz_multigrid_sessions`: `xyz_rgb_sessions` over a
deployment whose layers read bands of two resolutions (Sentinel-2's
20 m SWIR beside its 10 m NIR and green): a three-band layer the window
walks, and an expression layer the check holds as well.

The sessions, the twins, the window and the sample of the three-band
layer are `xyz_rgb_sessions`', with its parameters.  What is added:

- the prefill sends one tile a granule for EVERY layer of the
  configuration, because they read different bands (NBR reads B12, the
  false colour does not);
- the check holds `check.tiles` tiles of the window to
  `reference_rgb.py` as `xyz_rgb_sessions` does, and `check.nbr_tiles`
  tiles of the configuration's expression layer, fetched outside the
  window over tiles the window served, half of them over several
  granules, to `reference_expr.py` (palette and bytes as
  `xyz_expr_sessions` holds them), each fetched twice.

Refuses, before the server starts, a program that has no band sets over
several pixel grids: there every tile of this cell goes to the modular
route, which may run for minutes, and its expression to the unfused
leg, which stacks the rasters on the device.
"""

import io

import numpy as np

from .. import reference, reference_expr
from . import xyz_expr_sessions, xyz_rgb_sessions, xyz_sessions


class Generator(xyz_rgb_sessions.Generator):
    def __init__(self, traffic, config, archive, seed):
        import importlib
        executor = importlib.import_module("gsky_tpu.pipeline.executor")
        if not hasattr(executor, "_grid_sets"):
            raise SystemExit(
                "benchmark: this program forms no band set over several "
                "pixel grids (gsky_tpu.pipeline.executor._grid_sets); "
                "every tile of this cell would take its modular route, "
                "so the cell is not run on it")
        super().__init__(traffic, config, archive, seed)
        self._touched = {}

    # the expression layer's variables, as xyz_expr_sessions reads them
    _expression = xyz_expr_sessions.Generator._expression
    _per_var = xyz_expr_sessions.Generator._per_var
    want = xyz_expr_sessions.Generator.want

    def _is_expression(self, layer):
        return "=" in self.layers[layer]["rgb_products"][0]

    def _channels(self, layer, time):
        """One list of granules a channel, or a variable in the order
        the expression first reads them."""
        if self._is_expression(layer):
            return list(self._per_var(layer, time).values())
        return super()._channels(layer, time)

    def granules_touched(self, layer, time, bbox):
        """`xyz_rgb_sessions`', kept: the check samples the window twice."""
        key = (layer, time, bbox)
        if key not in self._touched:
            self._touched[key] = super().granules_touched(layer, time, bbox)
        return self._touched[key]

    def prefill(self):
        """One tile in the middle of every granule, at the finest level,
        for every layer of the configuration, so that every band any
        layer reads is resident before the first twin.  Sent as twins."""
        z = self.zooms[-1]
        size = xyz_sessions.WORLD / (1 << z)
        time = self.dates[0]
        reqs = []
        for layer in self.layers:
            for s in self._channels(layer, time)[0]:
                mx, my = reference.project(
                    np.array([s.x0 + s.dx * s.shape[1] / 2]),
                    np.array([s.y0 + s.dy * s.shape[0] / 2]),
                    s.crs, "EPSG:3857")
                reqs.append(self._req(
                    layer, z, int((mx[0] + xyz_sessions.WORLD / 2) // size),
                    int((xyz_sessions.WORLD / 2 - my[0]) // size), time))
        return self.twins(reqs)

    def _expression_tiles(self, results):
        """`check.nbr_tiles` requests of the expression layer over tiles
        the window served over data and the three-band check does not
        take, half over several granules."""
        layer = next(n for n in self.layers if self._is_expression(n))
        taken = {r.req.key for r in self._sample(results,
                                                 self.t["check"]["tiles"])}
        left = [r for r in results if r.req.key not in taken
                and self.granules_touched(layer, r.req.meta["time"],
                                          r.req.meta["bbox"])]
        return [self._req(layer, r.req.meta["z"], r.req.key[2],
                          r.req.key[3], r.req.meta["time"])
                for r in self._sample(left, self.t["check"]["nbr_tiles"])]

    def verify(self, results, fetch):
        """(problems, records): the three-band tiles as
        `xyz_rgb_sessions` checks them, then the expression layer's."""
        from PIL import Image
        problems, records = super().verify(results, fetch)
        bound = self.t["check"]["bound_mismatch"]
        for req in self._expression_tiles(results):
            lay = self.layers[req.meta["layer"]]
            rec = {"layer": lay["name"], "z": req.meta["z"],
                   "time": req.meta["time"],
                   "granules": self.granules_touched(
                       lay["name"], req.meta["time"], req.meta["bbox"])}
            records.append(rec)
            first, res = fetch(req), fetch(req)
            if not (first.ok and res.ok):
                problems.append(f"tile {req.key}: status {first.status}, "
                                f"{res.status}")
                continue
            rec["served_twice"] = res.digest != first.digest
            if rec["served_twice"]:
                problems.append(f"tile {req.key}: served twice, two answers")
            img = Image.open(io.BytesIO(res.body))
            if not xyz_expr_sessions._palette_is(img,
                                                 lay["palette"]["colours"]):
                problems.append(f"tile {req.key}: not a paletted PNG with "
                                "the configured ramp, entry 255 transparent")
                continue
            got = np.asarray(img)
            want = self.want(req)
            if got.shape != want.shape:
                problems.append(f"tile {req.key}: shape {got.shape}")
                continue
            rec.update(data_fraction=float(np.mean(want != 255)),
                       **reference_expr.compare(got, want))
            if rec["mismatch"] > bound:
                problems.append(
                    f"tile {req.key}: {rec['mismatch']:.3%} of bytes differ "
                    f"from the reference (bound {bound:.2%})")
        return problems, records
