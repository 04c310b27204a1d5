"""The sample archive the soak, `tools/accept.py` and `tools/demo.sh`
serve: four overlapping Landsat-style scenes, seeded."""

from __future__ import annotations

import os

N_SCENES = 4
SCENE_SIZE = 1536        # 1536x1536 int16 per scene, 30 m pixels


def build_archive(root):
    """Overlapping single-band Landsat-style UTM scenes on consecutive
    days, each shifted a third of a scene east and a fifth south.
    Returns (store, utm, paths)."""
    import numpy as np

    from gsky_tpu.geo.crs import parse_crs
    from gsky_tpu.geo.transform import GeoTransform
    from gsky_tpu.index import MASStore
    from gsky_tpu.index.crawler import extract
    from gsky_tpu.io import write_geotiff

    utm = parse_crs("EPSG:32755")
    rng = np.random.default_rng(42)
    paths = []
    for i in range(N_SCENES):
        gt = GeoTransform(590000.0 + i * SCENE_SIZE * 30 // 3, 30.0, 0.0,
                          6105000.0 - i * SCENE_SIZE * 30 // 5, 0.0, -30.0)
        data = rng.uniform(200, 3000, (SCENE_SIZE, SCENE_SIZE)).astype(
            np.int16)
        data[: SCENE_SIZE // 8, : SCENE_SIZE // 8] = -999
        date = f"2020-01-{10 + i:02d}"
        p = os.path.join(root, f"LC08_{date.replace('-', '')}_T1.tif")
        write_geotiff(p, data, gt, utm, nodata=-999)
        paths.append(p)
    store = MASStore()
    for p in paths:
        rec = extract(p)
        assert not rec.get("error"), rec
        store.ingest(rec)
    return store, utm, paths
