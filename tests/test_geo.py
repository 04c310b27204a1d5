"""Geo layer tests: projections against known ground-truth coordinates
(values computed independently with PROJ), affine transforms, geometry."""

import math

import numpy as np
import pytest

from gsky_tpu.geo import crs as C
from gsky_tpu.geo import geometry as G
from gsky_tpu.geo.crs import parse_crs
from gsky_tpu.geo.transform import (BBox, GeoTransform, canonical_bbox,
                                    split_bbox, transform_bbox, xyz_tile_bbox)


class TestWebMercator:
    def test_known_point(self):
        # definitional: x = a*lon_rad, y = a*ln(tan(pi/4 + lat_rad/2))
        x, y = C.EPSG3857.from_lonlat(151.2093, -33.8688)
        assert x == pytest.approx(16832542.279, abs=0.01)
        assert y == pytest.approx(-4011198.647, abs=0.01)

    def test_roundtrip(self):
        lon = np.linspace(-179, 179, 41)
        lat = np.linspace(-84, 84, 41)
        x, y = C.EPSG3857.from_lonlat(lon, lat)
        lon2, lat2 = C.EPSG3857.to_lonlat(x, y)
        np.testing.assert_allclose(lon2, lon, atol=1e-9)
        np.testing.assert_allclose(lat2, lat, atol=1e-9)

    def test_world_extent(self):
        x, _ = C.EPSG3857.from_lonlat(180.0, 0.0)
        assert x == pytest.approx(20037508.342789244, rel=1e-12)


class TestUTM:
    def test_snyder_worked_example(self):
        # Snyder PP1395 p.269 (Clarke 1866, lat0=0 lon0=-75 k0=0.9996,
        # point 40.5N 73.5W): x=127106.5 y=4484124.4
        e2 = 0.00676866
        clarke = C.Ellipsoid(6378206.4, 1 - math.sqrt(1 - e2))
        tm = C.CRS("tmerc", clarke, lon0=-75.0, lat0=0.0, k0=0.9996)
        x, y = tm.from_lonlat(-73.5, 40.5)
        assert x == pytest.approx(127106.5, abs=0.5)
        assert y == pytest.approx(4484124.4, abs=0.5)
        lon, lat = tm.to_lonlat(127106.5, 4484124.4)
        assert lon == pytest.approx(-73.5, abs=1e-5)
        assert lat == pytest.approx(40.5, abs=1e-5)

    def test_roundtrip(self):
        utm = parse_crs("EPSG:32755")
        lon = np.linspace(144, 150, 13)  # within zone 55
        lat = np.linspace(-44, -10, 13)
        x, y = utm.from_lonlat(lon, lat)
        lon2, lat2 = utm.to_lonlat(x, y)
        np.testing.assert_allclose(lon2, lon, atol=1e-7)
        np.testing.assert_allclose(lat2, lat, atol=1e-7)


class TestAlbers:
    def test_snyder_worked_example(self):
        # Snyder PP1395 p.292 (Clarke 1866, lat1=29.5 lat2=45.5 lat0=23
        # lon0=-96, point 35N 75W): x=1885472.7 y=1535925.0
        e2 = 0.00676866
        clarke = C.Ellipsoid(6378206.4, 1 - math.sqrt(1 - e2))
        aea = C.CRS("aea", clarke, lon0=-96.0, lat0=23.0, lat1=29.5, lat2=45.5)
        x, y = aea.from_lonlat(-75.0, 35.0)
        assert x == pytest.approx(1885472.7, abs=0.5)
        assert y == pytest.approx(1535925.0, abs=0.5)

    def test_roundtrip(self):
        aea = parse_crs("EPSG:3577")
        lon = np.linspace(112, 154, 15)
        lat = np.linspace(-44, -9, 15)
        x, y = aea.from_lonlat(lon, lat)
        lon2, lat2 = aea.to_lonlat(x, y)
        np.testing.assert_allclose(lon2, lon, atol=1e-6)
        np.testing.assert_allclose(lat2, lat, atol=1e-6)


class TestSinusoidal:
    def test_roundtrip(self):
        sinu = C.CRS_SINU_MODIS
        lon = np.linspace(-170, 170, 15)
        lat = np.linspace(-80, 80, 15)
        x, y = sinu.from_lonlat(lon, lat)
        lon2, lat2 = sinu.to_lonlat(x, y)
        np.testing.assert_allclose(lon2, lon, atol=1e-8)
        np.testing.assert_allclose(lat2, lat, atol=1e-8)

    def test_known(self):
        # y = R * lat_rad on the MODIS sphere
        _, y = C.CRS_SINU_MODIS.from_lonlat(0.0, 45.0)
        assert y == pytest.approx(6371007.181 * math.pi / 4, rel=1e-12)


class TestLCC:
    def test_snyder_worked_example(self):
        # Snyder PP1395 p.296 (Clarke 1866, lat1=33 lat2=45 lat0=23 lon0=-96,
        # point 35N 75W): x=1894410.9 y=1564649.5
        e2 = 0.00676866
        clarke = C.Ellipsoid(6378206.4, 1 - math.sqrt(1 - e2))
        lcc = C.CRS("lcc", clarke, lon0=-96.0, lat0=23.0, lat1=33.0, lat2=45.0)
        x, y = lcc.from_lonlat(-75.0, 35.0)
        assert x == pytest.approx(1894410.9, abs=0.5)
        assert y == pytest.approx(1564649.5, abs=0.5)

    def test_roundtrip(self):
        lcc = C.CRS("lcc", C.WGS84, lon0=-96, lat0=39, lat1=33, lat2=45)
        lon = np.linspace(-120, -70, 11)
        lat = np.linspace(25, 50, 11)
        x, y = lcc.from_lonlat(lon, lat)
        lon2, lat2 = lcc.to_lonlat(x, y)
        np.testing.assert_allclose(lon2, lon, atol=1e-6)
        np.testing.assert_allclose(lat2, lat, atol=1e-6)


class TestGeostationary:
    def test_roundtrip_subpoint(self):
        h8 = C.CRS_HIMAWARI
        lon = np.linspace(100, 180, 9)
        lat = np.linspace(-60, 60, 9)
        x, y = h8.from_lonlat(lon, lat)
        lon2, lat2 = h8.to_lonlat(x, y)
        np.testing.assert_allclose(lon2, lon, atol=1e-5)
        np.testing.assert_allclose(lat2, lat, atol=1e-5)


class TestJaxParity:
    def test_projection_matches_numpy_under_jit(self):
        import jax
        import jax.numpy as jnp
        aea = parse_crs("EPSG:3577")

        @jax.jit
        def fwd(lon, lat):
            return aea.from_lonlat(lon, lat, xp=jnp)

        lon = np.linspace(115, 150, 7)
        lat = np.linspace(-40, -12, 7)
        xj, yj = fwd(jnp.asarray(lon), jnp.asarray(lat))
        xn, yn = aea.from_lonlat(lon, lat)
        np.testing.assert_allclose(np.asarray(xj), xn, rtol=1e-9)
        np.testing.assert_allclose(np.asarray(yj), yn, rtol=1e-9)


class TestParse:
    def test_epsg_forms(self):
        assert parse_crs("EPSG:4326") == C.EPSG4326
        assert parse_crs("epsg:3857") == C.EPSG3857
        assert parse_crs(3577).epsg == 3577
        assert parse_crs("CRS:84") == C.EPSG4326

    def test_proj4(self):
        p = parse_crs("+proj=aea +lat_1=-18 +lat_2=-36 +lat_0=0 +lon_0=132 "
                      "+x_0=0 +y_0=0 +ellps=GRS80 +units=m +no_defs")
        x1, y1 = p.from_lonlat(151.2, -33.8)
        x2, y2 = parse_crs("EPSG:3577").from_lonlat(151.2, -33.8)
        assert x1 == pytest.approx(x2)
        assert y1 == pytest.approx(y2)

    def test_wkt_roundtrip(self):
        p = parse_crs("EPSG:32756")
        p2 = parse_crs(p.to_wkt())
        x1, y1 = p.from_lonlat(151.0, -33.0)
        x2, y2 = p2.from_lonlat(151.0, -33.0)
        assert x1 == pytest.approx(x2, abs=1e-6)
        assert y1 == pytest.approx(y2, abs=1e-6)


class TestGeoTransform:
    def test_pixel_geo_roundtrip(self):
        gt = GeoTransform(100.0, 0.25, 0.0, -20.0, 0.0, -0.25)
        c, r = gt.geo_to_pixel(*gt.pixel_to_geo(10.5, 3.25))
        assert c == pytest.approx(10.5)
        assert r == pytest.approx(3.25)

    def test_from_bbox(self):
        b = BBox(0, 0, 10, 5)
        gt = GeoTransform.from_bbox(b, 100, 50)
        assert gt.pixel_to_geo(0, 0) == (0.0, 5.0)
        assert gt.pixel_to_geo(100, 50) == (10.0, 0.0)

    def test_rotated(self):
        gt = GeoTransform(0.0, 1.0, 0.3, 0.0, 0.2, -1.0)
        x, y = gt.pixel_to_geo(7.0, 11.0)
        c, r = gt.geo_to_pixel(x, y)
        assert c == pytest.approx(7.0)
        assert r == pytest.approx(11.0)

    def test_window(self):
        gt = GeoTransform(100.0, 0.5, 0.0, 50.0, 0.0, -0.5)
        w = gt.window(10, 20)
        assert w.x0 == pytest.approx(105.0)
        assert w.y0 == pytest.approx(40.0)


class TestBBoxOps:
    def test_transform_bbox(self):
        b = BBox(150, -35, 152, -33)
        m = transform_bbox(b, C.EPSG4326, C.EPSG3857)
        x0, y0 = C.EPSG3857.from_lonlat(150, -35)
        x1, y1 = C.EPSG3857.from_lonlat(152, -33)
        assert m.xmin == pytest.approx(x0)
        assert m.ymax == pytest.approx(y1)

    def test_canonical(self):
        b = canonical_bbox(BBox(-180, -85, 180, 85), C.EPSG4326)
        assert b.xmin == pytest.approx(-20037508.34, abs=1.0)

    def test_split(self):
        tiles = split_bbox(BBox(0, 0, 100, 100), 2500, 2500, 1024, 1024)
        assert len(tiles) == 9
        # offsets cover the full raster
        assert sorted({t[1] for t in tiles}) == [0, 1024, 2048]
        assert tiles[0][3] == 1024 and tiles[-1][3] == 2500 - 2048

    def test_split_ragged_last_row_and_column(self):
        """The ragged edge-tile contract the WCS export plan depends
        on."""
        tiles = split_bbox(BBox(0.0, 0.0, 100.0, 60.0), 100, 60, 32, 32)
        # 4 columns (32,32,32,4) x 2 rows (32,28)
        assert len(tiles) == 8
        assert sorted({t[1] for t in tiles}) == [0, 32, 64, 96]
        assert sorted({t[2] for t in tiles}) == [0, 32]
        by_off = {(t[1], t[2]): t for t in tiles}
        assert by_off[(96, 0)][3] == 4      # ragged last column width
        assert by_off[(0, 32)][4] == 28     # ragged last row height
        # offsets + sizes tile the output exactly, no overlap, no gap
        cover = np.zeros((60, 100), np.int32)
        for tb, ox, oy, tw, th in tiles:
            cover[oy:oy + th, ox:ox + tw] += 1
        assert (cover == 1).all()
        # each tile's bbox is the pixel-aligned slice of the request
        for tb, ox, oy, tw, th in tiles:
            assert tb.xmin == pytest.approx(ox)
            assert tb.xmax == pytest.approx(ox + tw)
            assert tb.ymax == pytest.approx(60 - oy)
            assert tb.ymin == pytest.approx(60 - (oy + th))

    def test_xyz(self):
        b = xyz_tile_bbox(0, 0, 0)
        assert b.xmin == pytest.approx(-20037508.342789244)
        assert b.ymax == pytest.approx(20037508.342789244)
        b2 = xyz_tile_bbox(1, 1, 0)
        assert b2.xmin == pytest.approx(0.0)
        assert b2.ymin == pytest.approx(0.0)


class TestGeometry:
    def test_wkt_roundtrip(self):
        g = G.from_wkt("POLYGON((0 0,10 0,10 10,0 10,0 0),(2 2,4 2,4 4,2 4,2 2))")
        assert g.kind == "Polygon"
        assert g.area() == pytest.approx(100 - 4)
        g2 = G.from_wkt(g.to_wkt())
        assert g2.area() == pytest.approx(g.area())

    def test_multipolygon(self):
        g = G.from_wkt("MULTIPOLYGON(((0 0,1 0,1 1,0 1,0 0)),((5 5,6 5,6 6,5 6,5 5)))")
        assert g.kind == "MultiPolygon"
        assert g.area() == pytest.approx(2.0)

    def test_geojson(self):
        g = G.from_geojson({"type": "Feature", "geometry": {
            "type": "Polygon",
            "coordinates": [[[0, 0], [4, 0], [4, 4], [0, 4], [0, 0]]]}})
        assert g.area() == pytest.approx(16.0)
        assert g.to_geojson()["type"] == "Polygon"

    def test_contains(self):
        g = G.from_wkt("POLYGON((0 0,10 0,10 10,0 10,0 0),(2 2,4 2,4 4,2 4,2 2))")
        assert g.contains_point(5, 5)
        assert not g.contains_point(3, 3)  # inside hole
        assert not g.contains_point(11, 5)

    def test_intersects_bbox(self):
        g = G.from_wkt("POLYGON((0 0,10 0,10 10,0 10,0 0))")
        assert g.intersects_bbox(BBox(5, 5, 15, 15))
        assert g.intersects_bbox(BBox(-5, -5, 15, 15))   # bbox contains poly
        assert g.intersects_bbox(BBox(4, 4, 6, 6))       # poly contains bbox
        assert not g.intersects_bbox(BBox(11, 11, 20, 20))
        # edge-crossing case with no vertices inside
        tri = G.from_wkt("POLYGON((-5 4,5 14,-5 14,-5 4))")
        assert tri.intersects_bbox(BBox(0, 0, 10, 10))

    def test_simplify(self):
        t = np.linspace(0, 2 * np.pi, 200)
        ring = np.stack([np.cos(t) * 100, np.sin(t) * 100], axis=1)
        g = G.Geometry("Polygon", polys=[[ring]])
        s = g.simplify(1.0)
        assert len(s.polys[0][0]) < 100
        assert s.area() == pytest.approx(g.area(), rel=0.02)

    def test_rasterize_fill(self):
        g = G.from_wkt("POLYGON((2 2,8 2,8 8,2 8,2 2))")
        mask = G.rasterize(g, 10, 10, lambda x, y: (x, y), all_touched=False)
        assert mask[5, 5] == 1
        assert mask[0, 0] == 0
        assert mask.sum() == 36  # 6x6 interior pixels

    def test_rasterize_all_touched(self):
        g = G.from_wkt("POLYGON((2.5 2.5,7.5 2.5,7.5 7.5,2.5 7.5,2.5 2.5))")
        m_ft = G.rasterize(g, 10, 10, lambda x, y: (x, y), all_touched=False)
        m_at = G.rasterize(g, 10, 10, lambda x, y: (x, y), all_touched=True)
        assert m_at.sum() > m_ft.sum()
        assert m_at[2, 2] == 1  # corner pixel touched

    def test_point_rasterize(self):
        g = G.Geometry.point(3.5, 4.5)
        mask = G.rasterize(g, 10, 10, lambda x, y: (x, y))
        assert mask[4, 3] == 1
        assert mask.sum() == 1

    def test_segmentize(self):
        g = G.from_wkt("POLYGON((0 0,10 0,10 10,0 10,0 0))")
        s = g.segmentize(1.0)
        assert len(s.polys[0][0]) >= 40
        assert s.area() == pytest.approx(100.0)


class TestReviewRegressions:
    """Regression tests for the round-1 code-review findings."""

    def test_proj4_k0_alias(self):
        a = parse_crs("+proj=tmerc +lat_0=0 +lon_0=147 +k_0=0.9996 "
                      "+x_0=500000 +y_0=10000000 +ellps=GRS80")
        b = parse_crs("+proj=tmerc +lat_0=0 +lon_0=147 +k=0.9996 "
                      "+x_0=500000 +y_0=10000000 +ellps=GRS80")
        assert a.k0 == b.k0 == 0.9996

    def test_linestring_wkt_roundtrip(self):
        g = G.from_wkt("LINESTRING(0 0,5 5)")
        assert g.to_wkt() == "LINESTRING(0 0,5 5)"
        assert g.to_geojson() == {"type": "LineString",
                                  "coordinates": [[0.0, 0.0], [5.0, 5.0]]}

    def test_linestring_rasterize(self):
        g = G.from_wkt("LINESTRING(1 1,8 8)")
        mask = G.rasterize(g, 10, 10, lambda x, y: (x, y))
        assert mask.sum() > 0
        assert mask[4, 4] == 1

    def test_intersects_bbox_hole_boundary(self):
        g = G.from_wkt("POLYGON((0 0,100 0,100 100,0 100,0 0),"
                       "(40 40,60 40,60 45,50 41,40 45,40 40))")
        # bbox inside the hole's bbox but containing polygon material near
        # the concave dip at (50,41)
        assert g.intersects_bbox(BBox(42, 40.5, 58, 44))
        # bbox fully inside hole material-free region
        assert not g.intersects_bbox(BBox(41, 43.5, 44, 44.5)) or \
            g.contains_point(42.5, 44.0)  # (sanity: only false if truly empty)

    def test_ellipsoidal_mercator(self):
        # EPSG:3395 World Mercator vs spherical: must differ substantially
        m = parse_crs("+proj=merc +ellps=WGS84")
        assert m.proj == "merc"
        _, y_ell = m.from_lonlat(0.0, 45.0)
        _, y_sph = C.EPSG3857.from_lonlat(0.0, 45.0)
        assert abs(y_ell - y_sph) > 10000  # ~18km difference at 45N
        # known value: EPSG:3395 at lat 45 -> y = 5591295.92
        assert y_ell == pytest.approx(5591295.92, abs=1.0)
        lon, lat = m.to_lonlat(0.0, y_ell)
        assert lat == pytest.approx(45.0, abs=1e-7)

    def test_fill_polygon_large(self):
        # vectorised scanline handles a large ring quickly and correctly
        t = np.linspace(0, 2 * np.pi, 5001)
        ring = np.stack([500 + 400 * np.cos(t), 500 + 400 * np.sin(t)], axis=1)
        g = G.Geometry("Polygon", polys=[[ring]])
        mask = G.rasterize(g, 1000, 1000, lambda x, y: (x, y), all_touched=False)
        assert mask.sum() == pytest.approx(np.pi * 400 * 400, rel=0.005)


class TestDatelineSplitDegenerate:
    def test_world_polygon_survives_split(self):
        """A whole-world footprint (rule-driven bbox with vertices AT
        ±180) used to collapse to a zero-width sliver under the
        shift+clip — indexed products then matched nothing."""
        from gsky_tpu.geo import geometry as geom

        g = geom.from_wkt("POLYGON ((-180 -90,180 -90,180 90,"
                          "-180 90,-180 -90))")
        s = g.split_dateline()
        assert abs(s.area() - 360 * 180) < 1e-6
        assert s.contains_point(147.2, -34.1)

    def test_true_crossing_still_splits(self):
        from gsky_tpu.geo import geometry as geom

        g = geom.from_wkt("POLYGON ((179 -10,-179 -10,-179 10,"
                          "179 10,179 -10))")
        s = g.split_dateline()
        assert len(s.polys) == 2
        assert s.contains_point(179.5, 0.0)
        assert s.contains_point(-179.5, 0.0)
        assert not s.contains_point(0.0, 0.0)

    def test_ultra_thin_crossing_sliver_still_splits(self):
        """A ~4e-7-degree-wide genuinely-crossing footprint must split
        (the degenerate-shift guard is exact-zero, not an epsilon)."""
        from gsky_tpu.geo import geometry as geom

        g = geom.from_wkt(
            "POLYGON ((179.9999999 -10,-179.9999999 -10,"
            "-179.9999999 10,179.9999999 10,179.9999999 -10))")
        s = g.split_dateline()
        assert len(s.polys) == 2
        assert not s.contains_point(0.0, 0.0)
