"""Generator kind `xyz_expr_sessions`: `xyz_sessions` over band-expression
layers (NDVI, EVI: one output computed from two or three bands).

The sessions, the twins, the window, the sample (half of it from tiles
over several granules) and `granules_touched` are `xyz_rgb_sessions`',
with its parameters.  What differs is what a tile is made of and held
to: a layer's one `rgb_products` entry is `name = expression`, its
variables name the band namespaces, and the served one-band paletted
PNG is compared, index for index, with `reference_expr.py`; its colour
table must be the configured ramp with entry 255 transparent.  The
prefill sends one tile a granule for EVERY layer, because the layers
read different bands (EVI reads blue, NDVI does not).
"""

import io

import numpy as np

from .. import reference, reference_expr
from . import xyz_rgb_sessions, xyz_sessions
from .xyz_sessions import _unix


class Generator(xyz_rgb_sessions.Generator):
    def __init__(self, traffic, config, archive, seed):
        """Refuses, before the server starts, a program that has no
        fused expression kernel for the chip: at granule size its
        unfused leg stacks 0.97-3.88 GB of rasters a tile beside 5.8 GB
        of resident scenes, exhausts the device's memory and then the
        host's (PR 35's parent: warm-up tiles fail with
        RESOURCE_EXHAUSTED, the run is killed at 40 GiB of host memory
        after 426 s; PERF.md section 6).  Such a run measures nothing
        and must not be left to be killed."""
        import importlib
        # `gsky_tpu.ops.warp` the module: the package exports a function
        # of that name too
        warp = importlib.import_module("gsky_tpu.ops.warp")
        if not hasattr(warp, "render_expr_ctrl"):
            raise SystemExit(
                "benchmark: this program has no fused band-expression "
                "kernel (gsky_tpu.ops.warp.render_expr_ctrl); its unfused "
                "leg runs out of memory at granule size, so the cell is "
                "not run on it")
        super().__init__(traffic, config, archive, seed)

    def _expression(self, layer):
        """The text right of `=` in the layer's one product."""
        product, = self.layers[layer]["rgb_products"]
        return reference_expr.split_product(product)[1]

    def _per_var(self, layer, time):
        text = self._expression(layer)
        return reference_expr.select_vars(
            self.sources, reference_expr.variables(
                reference_expr.parse(text)), _unix(time))

    def _channels(self, layer, time):
        """One list of granules per variable, in the order the
        expression first reads them."""
        return list(self._per_var(layer, time).values())

    def prefill(self):
        """One tile in the middle of every granule, at the finest level,
        for every layer, so that every band any layer reads is resident
        before the first twin.  Sent as twins, so the window's own tiles
        stay new to the process."""
        z = self.zooms[-1]
        size = xyz_sessions.WORLD / (1 << z)
        time = self.dates[0]
        reqs = []
        for layer in self.t["layers"]:
            for s in self._channels(layer, time)[0]:
                mx, my = reference.project(
                    np.array([s.x0 + s.dx * s.shape[1] / 2]),
                    np.array([s.y0 + s.dy * s.shape[0] / 2]),
                    s.crs, "EPSG:3857")
                reqs.append(self._req(
                    layer, z, int((mx[0] + xyz_sessions.WORLD / 2) // size),
                    int((xyz_sessions.WORLD / 2 - my[0]) // size), time))
        return self.twins(reqs)

    def want(self, req):
        """The reference's byte plane for a request of this generator."""
        lay = self.layers[req.meta["layer"]]
        return reference_expr.render_byte(
            self._expression(lay["name"]),
            self._per_var(lay["name"], req.meta["time"]),
            req.meta["bbox"], "EPSG:3857", 256, 256,
            lay.get("resample", "near"), lay["offset_value"],
            lay["scale_value"], lay["clip_value"])

    def verify(self, results, fetch):
        """(problems, records): a sample of the window's tiles, asked
        for again outside the window (the bytes must be the window's)
        and compared, as the PNG's palette indices, with
        `reference_expr.py`."""
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
            if not _palette_is(img, lay["palette"]["colours"]):
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


def _palette_is(img, colours):
    """The PNG is paletted, its colour table is `reference_expr.palette`
    entry for entry below 255, and index 255 is transparent."""
    if img.mode != "P":
        return False
    ramp = reference_expr.palette(colours)
    table = np.array(img.getpalette("RGB"), int).reshape(-1, 3)
    alpha = img.info.get("transparency")
    clear = alpha == 255 if isinstance(alpha, int) else \
        alpha is not None and len(alpha) == 256 and alpha[255] == 0
    return bool(len(table) == 256 and clear
                and (table[:255] == ramp[:255, :3]).all())
