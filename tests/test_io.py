"""IO layer tests: GeoTIFF reader/writer (cross-validated against PIL),
NetCDF3/NetCDF4 readers, CF parsing, PNG encoding."""

import io
import os

import numpy as np
import pytest
from PIL import Image

from gsky_tpu.geo.crs import EPSG4326, parse_crs
from gsky_tpu.geo.transform import BBox, GeoTransform
from gsky_tpu.io import GeoTIFF, write_geotiff, encode_png
from gsky_tpu.io.geotiff import GeoTIFFWriter, deflate_pool_stats
from gsky_tpu.io.netcdf import (NetCDF, cf_times_to_unix, crs_from_cf,
                                parse_cf_time_units, write_netcdf3)
from gsky_tpu.io.png import decode_png, empty_tile_png, encode_jpeg


@pytest.fixture
def tmp_tif(tmp_path):
    return str(tmp_path / "t.tif")


class TestGeoTIFFRoundtrip:
    def _roundtrip(self, tmp_tif, data, **kw):
        gt = GeoTransform(1000.0, 25.0, 0.0, 5000.0, 0.0, -25.0)
        crs = parse_crs("EPSG:32755")
        write_geotiff(tmp_tif, data, gt, crs, **kw)
        with GeoTIFF(tmp_tif) as g:
            if data.ndim == 2:
                got = g.read(1)
                np.testing.assert_array_equal(got, data)
            else:
                for b in range(data.shape[0]):
                    np.testing.assert_array_equal(g.read(b + 1), data[b])
            assert g.gt.x0 == 1000.0
            assert g.gt.dx == 25.0
            assert g.crs.epsg == 32755
        return tmp_tif

    def test_float32(self, tmp_tif):
        rng = np.random.default_rng(0)
        self._roundtrip(tmp_tif, rng.normal(size=(300, 200)).astype(np.float32))

    def test_uint8_multiband(self, tmp_tif):
        rng = np.random.default_rng(1)
        self._roundtrip(
            tmp_tif, rng.integers(0, 255, (3, 100, 130)).astype(np.uint8))

    def test_int16_nodata(self, tmp_tif):
        data = np.arange(-500, 500, dtype=np.int16).reshape(20, 50)
        gt = GeoTransform(0.0, 1.0, 0.0, 0.0, 0.0, -1.0)
        write_geotiff(tmp_tif, data, gt, EPSG4326, nodata=-32768)
        with GeoTIFF(tmp_tif) as g:
            assert g.nodata == -32768
            assert g.crs == EPSG4326
            np.testing.assert_array_equal(g.read(1), data)

    def test_uncompressed(self, tmp_tif):
        data = np.arange(64, dtype=np.uint16).reshape(8, 8)
        gt = GeoTransform(0.0, 1.0, 0.0, 0.0, 0.0, -1.0)
        write_geotiff(tmp_tif, data, gt, EPSG4326, compress=False)
        with GeoTIFF(tmp_tif) as g:
            np.testing.assert_array_equal(g.read(1), data)

    def test_window_read(self, tmp_tif):
        rng = np.random.default_rng(2)
        data = rng.integers(0, 1000, (700, 900)).astype(np.uint16)
        gt = GeoTransform(0.0, 1.0, 0.0, 700.0, 0.0, -1.0)
        write_geotiff(tmp_tif, data, gt, EPSG4326, tile_size=128)
        with GeoTIFF(tmp_tif) as g:
            win = g.read(1, (250, 130, 400, 300))
            np.testing.assert_array_equal(win, data[130:430, 250:650])

    def test_window_geo(self, tmp_tif):
        data = np.arange(10000, dtype=np.float32).reshape(100, 100)
        gt = GeoTransform(100.0, 1.0, 0.0, 100.0, 0.0, -1.0)
        write_geotiff(tmp_tif, data, gt, EPSG4326)
        with GeoTIFF(tmp_tif) as g:
            sub, wgt = g.read_window_geo(BBox(110, 50, 130, 80))
            assert sub.shape == (30, 20)
            assert wgt.x0 == 110.0
            assert wgt.y0 == 80.0
            np.testing.assert_array_equal(sub, data[20:50, 10:30])
            none, _ = g.read_window_geo(BBox(500, 500, 600, 600))
            assert none is None

    def test_proj4_fallback_crs(self, tmp_tif):
        crs = parse_crs("+proj=sinu +R=6371007.181")
        gt = GeoTransform(0.0, 500.0, 0.0, 0.0, 0.0, -500.0)
        write_geotiff(tmp_tif, np.zeros((4, 4), np.float32), gt, crs)
        with GeoTIFF(tmp_tif) as g:
            assert g.crs.proj == "sinu"


class TestGeoTIFFvsPIL:
    """Cross-validation against an independent TIFF implementation."""

    def test_pil_reads_our_tiles(self, tmp_tif):
        rng = np.random.default_rng(3)
        data = rng.integers(0, 255, (100, 150)).astype(np.uint8)
        gt = GeoTransform(0.0, 1.0, 0.0, 0.0, 0.0, -1.0)
        write_geotiff(tmp_tif, data, gt, EPSG4326, tile_size=64)
        img = Image.open(tmp_tif)
        np.testing.assert_array_equal(np.asarray(img), data)

    @pytest.mark.parametrize("comp", [None, "tiff_lzw", "tiff_adobe_deflate",
                                      "packbits"])
    def test_we_read_pil_strips(self, tmp_path, comp):
        rng = np.random.default_rng(4)
        data = rng.integers(0, 255, (90, 121)).astype(np.uint8)
        p = str(tmp_path / f"pil_{comp}.tif")
        img = Image.fromarray(data)
        if comp:
            img.save(p, compression=comp)
        else:
            img.save(p)
        with GeoTIFF(p) as g:
            np.testing.assert_array_equal(g.read(1), data)

    def test_we_read_pil_rgb(self, tmp_path):
        rng = np.random.default_rng(5)
        data = rng.integers(0, 255, (64, 80, 3)).astype(np.uint8)
        p = str(tmp_path / "rgb.tif")
        Image.fromarray(data, "RGB").save(p, compression="tiff_adobe_deflate")
        with GeoTIFF(p) as g:
            assert g.count == 3
            for b in range(3):
                np.testing.assert_array_equal(g.read(b + 1), data[..., b])

    def test_we_read_pil_float(self, tmp_path):
        data = np.linspace(0, 1, 48 * 50, dtype=np.float32).reshape(48, 50)
        p = str(tmp_path / "f32.tif")
        Image.fromarray(data, "F").save(p)
        with GeoTIFF(p) as g:
            np.testing.assert_allclose(g.read(1), data)


def _serial_geotiff(path, data, gt, crs, nodata, tile_size, compress):
    """The serial writer: each block cut, deflated and appended in
    row-major order on the calling thread."""
    w = GeoTIFFWriter(path, data.shape[0], data.shape[1], data.shape[2],
                      data.dtype, gt, crs, nodata=nodata,
                      tile_size=tile_size, compress=compress)
    ts = tile_size
    for ty in range(w.tiles_y):
        for tx in range(w.tiles_x):
            w.write_tile(tx, ty, data[:, ty * ts:(ty + 1) * ts,
                                      tx * ts:(tx + 1) * ts])
    w.close()


def _field(shape, dtype, seed):
    """Imagery-like: a smooth ramp plus noise, so deflate has work."""
    rng = np.random.default_rng(seed)
    b, h, w = shape
    base = np.add.outer(np.arange(h), np.arange(w))[None] * 0.25 \
        + rng.normal(0, 4, shape)
    if np.dtype(dtype).kind == "u":
        return (base % 200).astype(dtype)
    return (base - 300).astype(dtype)


def _read_bytes(path):
    with open(path, "rb") as fp:
        return fp.read()


class TestPooledDeflate:
    """A whole-image write deflates its blocks on the shared pool and
    gives the serial writer's file byte for byte."""

    GT = GeoTransform(1000.0, 25.0, 0.0, 5000.0, 0.0, -25.0)
    CRS = parse_crs("EPSG:32755")

    @pytest.mark.parametrize("compress", [True, False])
    @pytest.mark.parametrize("nodata", [None, -9999])
    @pytest.mark.parametrize("tile_size", [64, 128, 256])
    @pytest.mark.parametrize("dtype", ["float32", "int16", "uint8"])
    @pytest.mark.parametrize("bands", [1, 3])
    def test_byte_identical_to_serial(self, tmp_path, bands, dtype,
                                      tile_size, nodata, compress):
        if dtype == "uint8" and nodata is not None:
            nodata = 255            # -9999 has no uint8 value
        # 3 x 2 blocks, the last row and column partial
        data = _field((bands, 2 * tile_size + 37, tile_size + 19), dtype,
                      seed=bands + tile_size)
        ref, got = str(tmp_path / "serial.tif"), str(tmp_path / "pooled.tif")
        _serial_geotiff(ref, data, self.GT, self.CRS, nodata, tile_size,
                        compress)
        before = deflate_pool_stats()
        write_geotiff(got, data[0] if bands == 1 else data, self.GT,
                      self.CRS, nodata=nodata, tile_size=tile_size,
                      compress=compress)
        after = deflate_pool_stats()
        assert _read_bytes(got) == _read_bytes(ref)
        pooled = after["blocks_pooled"] - before["blocks_pooled"]
        assert pooled == (6 if compress else 0)
        assert after["writes"] - before["writes"] == int(compress)
        if compress:
            assert 1 <= after["workers"] <= 8
        with GeoTIFF(got) as g:
            for b in range(bands):
                np.testing.assert_array_equal(g.read(b + 1), data[b])

    def test_two_writes_at_once_each_give_their_own_file(self, tmp_path):
        import threading
        images = [_field((1, 700, 600), "float32", 1),
                  _field((3, 520, 530), "int16", 2)]
        alone = []
        for i, data in enumerate(images):
            p = str(tmp_path / f"alone{i}.tif")
            write_geotiff(p, data, self.GT, self.CRS, nodata=-9999)
            alone.append(_read_bytes(p))
        start = threading.Barrier(2)
        errors = []

        def write(i):
            try:
                start.wait()
                write_geotiff(str(tmp_path / f"both{i}.tif"), images[i],
                              self.GT, self.CRS, nodata=-9999)
            except Exception as exc:   # surfaced by the assert below
                errors.append(exc)
        threads = [threading.Thread(target=write, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        for i in range(2):
            assert _read_bytes(str(tmp_path / f"both{i}.tif")) == alone[i]

    @pytest.mark.parametrize("shape,compress,blocks", [
        ((200, 250), True, 1),          # one block: deflated in place
        ((600, 600), False, 0),         # uncompressed: a copy, not counted
    ])
    def test_serial_path_leaves_the_pool_out(self, tmp_tif, shape, compress,
                                             blocks):
        before = deflate_pool_stats()
        write_geotiff(tmp_tif, np.ones(shape, np.float32), self.GT,
                      self.CRS, compress=compress)
        after = deflate_pool_stats()
        assert after["blocks_pooled"] == before["blocks_pooled"]
        assert after["blocks"] - before["blocks"] == blocks
        assert after["writes"] - before["writes"] == int(compress)


class TestCFTime:
    def test_units(self):
        mult, epoch = parse_cf_time_units("days since 1970-01-01")
        assert mult == 86400.0 and epoch == 0.0
        mult, epoch = parse_cf_time_units("seconds since 2000-01-01 12:00:00")
        assert mult == 1.0
        assert epoch == 946728000.0

    def test_convert(self):
        t = cf_times_to_unix(np.array([0.0, 1.0]), "hours since 1970-01-02")
        np.testing.assert_allclose(t, [86400.0, 90000.0])

    def test_bad(self):
        with pytest.raises(ValueError):
            parse_cf_time_units("fortnights since forever")


class TestCFGridMapping:
    def test_albers(self):
        crs = crs_from_cf({
            "grid_mapping_name": "albers_conical_equal_area",
            "standard_parallel": np.array([-18.0, -36.0]),
            "longitude_of_central_meridian": 132.0,
            "latitude_of_projection_origin": 0.0,
            "false_easting": 0.0, "false_northing": 0.0,
            "semi_major_axis": 6378137.0,
            "inverse_flattening": 298.257222101,
        })
        ref = parse_crs("EPSG:3577")
        x1, y1 = crs.from_lonlat(145.0, -30.0)
        x2, y2 = ref.from_lonlat(145.0, -30.0)
        assert x1 == pytest.approx(x2, abs=1e-3)
        assert y1 == pytest.approx(y2, abs=1e-3)

    def test_spatial_ref_shortcut(self):
        crs = crs_from_cf({"spatial_ref": parse_crs("EPSG:32755").to_wkt()})
        assert crs.epsg == 32755


class TestNetCDF3:
    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "a.nc")
        rng = np.random.default_rng(6)
        data = rng.normal(size=(3, 40, 50)).astype(np.float32)
        x = np.linspace(100.25, 124.75, 50)
        y = np.linspace(-10.25, -29.75, 40)
        times = np.array([0.0, 86400.0, 172800.0])
        write_netcdf3(p, {"fc": data}, x, y, EPSG4326, times=times,
                      nodata=-999.0)
        with NetCDF(p) as nc:
            assert "fc" in nc.variables
            v = nc.variables["fc"]
            assert v.shape == (3, 40, 50)
            assert v.nodata == -999.0
            np.testing.assert_allclose(np.asarray(v[(1, slice(None), slice(None))]),
                                       data[1], rtol=1e-6)
            ts = nc.timestamps()
            np.testing.assert_allclose(ts, times)
            gt = nc.geotransform()
            assert gt.dx == pytest.approx(0.5)
            assert gt.x0 == pytest.approx(100.0)
            sl = nc.read_slice("fc", 2, (10, 5, 20, 12))
            np.testing.assert_allclose(sl, data[2, 5:17, 10:30], rtol=1e-6)

    def test_projected_crs(self, tmp_path):
        p = str(tmp_path / "b.nc")
        x = np.arange(10) * 25.0
        y = np.arange(8) * -25.0
        write_netcdf3(p, {"v": np.zeros((8, 10), np.int16)}, x, y,
                      parse_crs("EPSG:3577"))
        with NetCDF(p) as nc:
            crs = nc.crs(nc.variables["v"])
            assert crs.proj == "aea"
            assert crs.lon0 == 132.0


@pytest.mark.skipif(not pytest.importorskip("h5py"), reason="h5py missing")
class TestNetCDF4:
    def test_h5_file(self, tmp_path):
        import h5py
        p = str(tmp_path / "c.nc")
        rng = np.random.default_rng(7)
        data = rng.normal(size=(2, 30, 20)).astype(np.float32)
        with h5py.File(p, "w") as f:
            d = f.create_dataset("ndvi", data=data)
            d.attrs["_FillValue"] = np.float32(-1.0)
            d.attrs["grid_mapping"] = "crs"
            f.create_dataset("x", data=np.arange(20) * 0.1 + 140.0)
            f.create_dataset("y", data=-10.0 - np.arange(30) * 0.1)
            t = f.create_dataset("time", data=np.array([10.0, 11.0]))
            t.attrs["units"] = "days since 2020-01-01"
            t.attrs["standard_name"] = "time"
            c = f.create_dataset("crs", data=0)
            c.attrs["grid_mapping_name"] = "latitude_longitude"
        with NetCDF(p) as nc:
            v = nc.variables["ndvi"]
            assert v.nodata == -1.0
            ts = nc.timestamps()
            assert ts is not None and len(ts) == 2
            sl = nc.read_slice("ndvi", 1, (2, 3, 10, 12))
            np.testing.assert_allclose(sl, data[1, 3:15, 2:12])
            gt = nc.geotransform()
            assert gt.dx == pytest.approx(0.1)


class TestPNG:
    def test_paletted(self):
        img = np.array([[0, 100], [200, 255]], np.uint8)
        lut = np.zeros((256, 4), np.uint8)
        lut[:, 0] = np.arange(256)
        lut[:, 3] = 255
        lut[255] = (0, 0, 0, 0)
        png = encode_png([img], lut)
        rgba = decode_png(png)
        assert rgba.shape == (2, 2, 4)
        assert rgba[0, 0, 0] == 0
        assert rgba[1, 0, 0] == 200
        assert rgba[1, 1, 3] == 0  # nodata transparent

    def test_rgb(self):
        r = np.full((4, 4), 10, np.uint8)
        g = np.full((4, 4), 20, np.uint8)
        b = np.full((4, 4), 30, np.uint8)
        b[0, 0] = 255; r[0, 0] = 255; g[0, 0] = 255
        rgba = decode_png(encode_png([r, g, b]))
        assert tuple(rgba[1, 1][:3]) == (10, 20, 30)
        assert rgba[0, 0, 3] == 0  # all-255 pixel transparent

    def test_empty_tile(self):
        png = empty_tile_png(64, 32)
        rgba = decode_png(png)
        assert rgba.shape == (32, 64, 4)
        assert (rgba[..., 3] == 0).all()

    def test_jpeg(self):
        bands = [np.full((8, 8), v, np.uint8) for v in (50, 100, 150)]
        data = encode_jpeg(bands)
        assert data[:2] == b"\xff\xd8"


class TestNC3CrossValidation:
    """Cross-validate the classic-NetCDF reader/writer against scipy's
    independent implementation."""

    def test_read_scipy_single_record_var(self, tmp_path):
        # exactly one record variable: records are packed UNPADDED
        from scipy.io import netcdf_file
        p = str(tmp_path / "rec.nc")
        f = netcdf_file(p, "w")
        f.createDimension("time", None)
        f.createDimension("x", 3)
        v = f.createVariable("v", np.int16, ("time", "x"))
        data = np.arange(12, dtype=np.int16).reshape(4, 3)
        for i in range(4):
            v[i] = data[i]
        f.flush(); f.close()
        with NetCDF(p) as nc:
            got = nc.variables["v"][(slice(None), slice(None))]
            np.testing.assert_array_equal(got, data)
            got1 = nc.variables["v"][(2, slice(None))]
            np.testing.assert_array_equal(got1, data[2])

    def test_scipy_reads_our_writer(self, tmp_path):
        from scipy.io import netcdf_file
        p = str(tmp_path / "ours.nc")
        data = np.arange(24, dtype=np.float32).reshape(4, 6)
        x = np.linspace(0, 5, 6); y = np.linspace(0, 3, 4)
        write_netcdf3(p, {"band1": data}, x, y, EPSG4326, nodata=-1.0)
        f = netcdf_file(p, "r")
        np.testing.assert_allclose(f.variables["band1"][:], data)
        np.testing.assert_allclose(f.variables["x"][:], x)
        f.close()

    def test_unsigned_roundtrip(self, tmp_path):
        p = str(tmp_path / "u8.nc")
        data = np.array([[0, 127, 128, 255]], np.uint8)
        write_netcdf3(p, {"b": data}, np.arange(4.0), np.arange(1.0),
                      EPSG4326, nodata=255)
        with NetCDF(p) as nc:
            got = nc.variables["b"][(slice(None), slice(None))]
            assert got.dtype == np.uint8
            np.testing.assert_array_equal(got, data)
            assert nc.variables["b"].nodata == 255


class TestPredictors:
    def _make_tiff(self, tmp_path, data, predictor, dtype):
        """Hand-craft a single-strip little-endian TIFF with a predictor."""
        import struct as st
        h, w = data.shape
        if predictor == 2:
            enc = data.copy()
            enc[:, 1:] = data[:, 1:] - data[:, :-1]
            raw = enc.astype(dtype).tobytes()
        else:  # predictor 3 on float32
            be = data.astype(">f4").view(np.uint8).reshape(h, w, 4)
            planes = np.transpose(be, (0, 2, 1)).reshape(h, w * 4)
            enc = planes.copy()
            enc[:, 1:] = planes[:, 1:] - planes[:, :-1]
            raw = enc.tobytes()
        bits = np.dtype(dtype).itemsize * 8
        fmt = {"u": 1, "i": 2, "f": 3}[np.dtype(dtype).kind]
        tags = [
            (256, 3, [w]), (257, 3, [h]), (258, 3, [bits]), (259, 3, [1]),
            (262, 3, [1]), (273, 4, [8]), (277, 3, [1]), (278, 3, [h]),
            (279, 4, [len(raw)]), (317, 3, [predictor]), (339, 3, [fmt]),
        ]
        buf = b"II*\0" + st.pack("<I", 8 + len(raw))
        buf += raw
        buf += st.pack("<H", len(tags))
        for tag, typ, vals in tags:
            fmtc = {3: "H", 4: "I"}[typ]
            inline = st.pack("<" + fmtc * len(vals), *vals).ljust(4, b"\0")
            buf += st.pack("<HHI", tag, typ, len(vals)) + inline
        buf += st.pack("<I", 0)
        p = str(tmp_path / f"pred{predictor}.tif")
        open(p, "wb").write(buf)
        return p

    def test_predictor2_uint8(self, tmp_path):
        rng = np.random.default_rng(8)
        data = rng.integers(0, 255, (16, 32)).astype(np.uint8)
        p = self._make_tiff(tmp_path, data, 2, np.uint8)
        with GeoTIFF(p) as g:
            np.testing.assert_array_equal(g.read(1), data)

    def test_predictor2_uint16(self, tmp_path):
        rng = np.random.default_rng(9)
        data = rng.integers(0, 60000, (8, 20)).astype(np.uint16)
        p = self._make_tiff(tmp_path, data, 2, np.uint16)
        with GeoTIFF(p) as g:
            np.testing.assert_array_equal(g.read(1), data)

    def test_predictor3_float32(self, tmp_path):
        rng = np.random.default_rng(10)
        data = rng.normal(size=(6, 10)).astype(np.float32)
        p = self._make_tiff(tmp_path, data, 3, np.float32)
        with GeoTIFF(p) as g:
            np.testing.assert_array_equal(g.read(1), data)

    def test_predictor_python_fallback(self, tmp_path, monkeypatch):
        import gsky_tpu.io.geotiff as gtf
        rng = np.random.default_rng(11)
        data = rng.normal(size=(5, 7)).astype(np.float32)
        p = self._make_tiff(tmp_path, data, 3, np.float32)
        monkeypatch.setattr(gtf, "_native", None)
        with GeoTIFF(p) as g:
            np.testing.assert_array_equal(g.read(1), data)


class TestIOReviewRegressions:
    def test_default_png_nodata_transparent(self):
        img = np.array([[10, 255]], np.uint8)
        rgba = decode_png(encode_png([img]))
        assert rgba[0, 0, 3] == 255
        assert rgba[0, 1, 3] == 0  # nodata transparent by default

    def test_nc3_negative_and_oob_record_index(self, tmp_path):
        from scipy.io import netcdf_file
        p = str(tmp_path / "rec2.nc")
        f = netcdf_file(p, "w")
        f.createDimension("time", None)
        f.createDimension("x", 3)
        v = f.createVariable("v", np.int16, ("time", "x"))
        data = np.arange(12, dtype=np.int16).reshape(4, 3)
        for i in range(4):
            v[i] = data[i]
        f.flush(); f.close()
        with NetCDF(p) as nc:
            np.testing.assert_array_equal(
                nc.variables["v"][(-1, slice(None))], data[-1])
            with pytest.raises(IndexError):
                nc.variables["v"][(7, slice(None))]

    def test_south_up_geotiff_roundtrip(self, tmp_path):
        p = str(tmp_path / "southup.tif")
        gt = GeoTransform(0.0, 1.0, 0.0, 0.0, 0.0, 1.0)  # dy positive
        data = np.arange(12, dtype=np.float32).reshape(3, 4)
        write_geotiff(p, data, gt, EPSG4326)
        with GeoTIFF(p) as g:
            assert g.gt.dy == 1.0
            np.testing.assert_array_equal(g.read(1), data)

    def test_nc3_int64_overflow_raises(self, tmp_path):
        with pytest.raises(ValueError):
            write_netcdf3(str(tmp_path / "x.nc"),
                          {"t": np.array([[2 ** 40]], np.int64)},
                          np.arange(1.0), np.arange(1.0), EPSG4326)

    def test_nc3_fixed_var_partial_reads(self, tmp_path):
        """Fixed (non-record) 3-D variables must serve single-timestep
        and contiguous-range reads WITHOUT materialising the whole
        variable (regression: whole-stack read per access)."""
        p = str(tmp_path / "stack.nc")
        data = np.arange(5 * 4 * 3, dtype=np.float32).reshape(5, 4, 3)
        times = np.arange(5) * 86400.0
        write_netcdf3(p, {"v": data}, np.arange(3.0), np.arange(4.0),
                      EPSG4326, times)
        with NetCDF(p) as nc:
            v = nc.variables["v"]
            reads = []
            orig = nc._nc3.read_at

            def counting(pos, n):
                reads.append(n)
                return orig(pos, n)

            nc._nc3.read_at = counting
            np.testing.assert_array_equal(v[(2, slice(1, 3), slice(0, 2))],
                                          data[2, 1:3, 0:2])
            np.testing.assert_array_equal(v[(slice(1, 4), slice(None),
                                             slice(None))], data[1:4])
            np.testing.assert_array_equal(v[(-1, slice(None), slice(None))],
                                          data[-1])
            frame = 4 * 3 * 4  # one (y, x) frame in bytes
            assert reads == [frame, 3 * frame, frame], reads
            # negative-stride / fancy keys still fall back correctly
            np.testing.assert_array_equal(
                v[(slice(None, None, 2), slice(None), slice(None))],
                data[::2])

    def test_nc3_record_var_slice_spatial_window(self, tmp_path):
        """Record (unlimited-dim) variables: a slice time key plus
        spatial window must apply the window per record, not to the
        time axis (regression)."""
        from scipy.io import netcdf_file
        p = str(tmp_path / "rec.nc")
        data = np.arange(5 * 4 * 3, dtype=np.float32).reshape(5, 4, 3)
        f = netcdf_file(p, "w")
        f.createDimension("time", None)
        f.createDimension("y", 4)
        f.createDimension("x", 3)
        v = f.createVariable("v", np.float32, ("time", "y", "x"))
        v[:] = data
        f.close()
        with NetCDF(p) as nc:
            got = nc.variables["v"][(slice(1, 4), slice(1, 3),
                                     slice(0, 2))]
            np.testing.assert_array_equal(got, data[1:4, 1:3, 0:2])


class TestOverviews:
    """Embedded reduced-resolution IFDs: writer round-trip + selection
    (`worker/gdalprocess/warp.go:156-198` decode-path overview use)."""

    def _with_ovr(self, tmp_path, shape=(400, 300), factors=(2, 4)):
        rng = np.random.default_rng(3)
        data = rng.uniform(0, 3000, shape).astype(np.int16)
        data[:32, :32] = -999
        gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
        p = str(tmp_path / "ovr.tif")
        write_geotiff(p, data, gt, parse_crs("EPSG:32755"), nodata=-999,
                      overviews=factors)
        return p, data

    def test_roundtrip_factors_and_pixels(self, tmp_path):
        p, data = self._with_ovr(tmp_path)
        H, W = data.shape
        with GeoTIFF(p) as g:
            assert [f for f, _ in g.overviews] == [2, 4]
            for f, ifd in g.overviews:
                got = g.read(1, (0, 0, ifd.width, ifd.height), ifd=ifd)
                # centre-of-block sampling (readers georeference
                # overviews extent-preservingly)
                np.testing.assert_array_equal(
                    got,
                    data[f // 2::f, f // 2::f][:H // f, :W // f])
            # full-res read unaffected
            np.testing.assert_array_equal(g.read(1), data)

    def test_overview_registration(self, tmp_path):
        """An overview render must stay registered with full resolution:
        each decimated sample sits within half a SOURCE pixel of where
        the extent-preserving scaled geotransform claims it is (top-left
        sampling would be off by (f-1)/2 px and fail this).  The fixture
        encodes each pixel's own coordinates, so the sampled source
        pixel is exactly decodable."""
        cc, rr = np.meshgrid(np.arange(512), np.arange(512))
        data = (rr * 512 + cc).astype(np.int32)
        gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
        p = str(tmp_path / "reg.tif")
        write_geotiff(p, data, gt, parse_crs("EPSG:32755"),
                      overviews=(2, 4))
        with GeoTIFF(p) as g:
            for f, ifd in g.overviews:
                got = g.read(1, (0, 0, ifd.width, ifd.height), ifd=ifd)
                for k in (0, 5, ifd.width - 1):
                    src_row, src_col = divmod(int(got[k, k]), 512)
                    claimed = (k + 0.5) * f - 0.5   # full-res px coords
                    assert abs(src_row - claimed) <= 0.5 + 1e-9, \
                        (f, k, src_row, claimed)
                    assert abs(src_col - claimed) <= 0.5 + 1e-9

    def test_pick_overview(self, tmp_path):
        p, _ = self._with_ovr(tmp_path)
        with GeoTIFF(p) as g:
            assert g.pick_overview(1.5)[2] is None
            fx, fy, ifd = g.pick_overview(2.7)
            assert ifd.width == g.width // 2
            fx, fy, ifd = g.pick_overview(64.0)
            assert ifd.width == g.width // 4
            assert fx == g.width / ifd.width

    def test_pil_still_reads_main(self, tmp_path):
        """Overview chain must not confuse other readers' main image."""
        p, data = self._with_ovr(tmp_path, shape=(64, 64), factors=(2,))
        im = Image.open(p)
        np.testing.assert_array_equal(np.asarray(im), data)

    def test_decode_window_uses_overview(self, tmp_path):
        from gsky_tpu.pipeline.decode import decode_window
        from gsky_tpu.pipeline.types import Granule

        p, data = self._with_ovr(tmp_path, shape=(512, 512))
        gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
        g = Granule(path=p, ds_name=p, namespace="b1",
                    base_namespace="b1", band=1, time_index=None,
                    timestamp=0.0, geo_transform=list(gt.to_gdal()),
                    srs="EPSG:32755", nodata=-999.0)
        bbox = gt.bbox(512, 512)
        crs = parse_crs("EPSG:32755")
        # 512px of source rendered onto a 128px tile -> stride 4
        w = decode_window(g, bbox, crs, "near", dst_hw=(128, 128))
        assert w.data.shape[0] <= 130
        np.testing.assert_array_equal(
            w.data, data[2::4, 2::4][:128, :128].astype(np.float32))
        assert w.window_gt.dx == pytest.approx(30.0 * 4)
        # same request at full tile res -> full window
        w1 = decode_window(g, bbox, crs, "near", dst_hw=(512, 512))
        assert w1.data.shape[0] == 512
        assert w1.window_gt.dx == pytest.approx(30.0)

    def test_decode_window_netcdf_stride(self, tmp_path):
        from gsky_tpu.pipeline.decode import decode_window
        from gsky_tpu.pipeline.types import Granule

        rng = np.random.default_rng(4)
        H = W = 256
        data = rng.uniform(0, 1, (H, W)).astype(np.float32)
        xs = 148.0 + (np.arange(W) + 0.5) * 0.004
        ys = -35.0 - (np.arange(H) + 0.5) * 0.004
        p = str(tmp_path / "s.nc")
        write_netcdf3(p, {"v": data}, xs, ys, EPSG4326, nodata=-9999.0)
        gt = GeoTransform(148.0, 0.004, 0.0, -35.0, 0.0, -0.004)
        g = Granule(path=p, ds_name=p, namespace="v",
                    base_namespace="v", band=1, time_index=None,
                    timestamp=0.0, geo_transform=list(gt.to_gdal()),
                    srs="EPSG:4326", nodata=-9999.0, is_netcdf=True,
                    var_name="v")
        bbox = gt.bbox(W, H)
        w = decode_window(g, bbox, EPSG4326, "near", dst_hw=(64, 64))
        np.testing.assert_array_equal(w.data, data[::4, ::4])
        assert w.window_gt.dx == pytest.approx(0.004 * 4)
        # decimated pixel centres must still land on the sampled source
        # pixel centres: centre of output pixel 0 == centre of src pixel 0
        x, y = w.window_gt.pixel_to_geo(0.5, 0.5)
        assert x == pytest.approx(148.0 + 0.5 * 0.004)
        assert y == pytest.approx(-35.0 - 0.5 * 0.004)

    def test_scene_cache_levels(self, tmp_path):
        from gsky_tpu.pipeline.scene_cache import SceneCache
        from gsky_tpu.pipeline.types import Granule

        p, data = self._with_ovr(tmp_path, shape=(512, 512))
        gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
        g = Granule(path=p, ds_name=p, namespace="b1",
                    base_namespace="b1", band=1, time_index=None,
                    timestamp=0.0, geo_transform=list(gt.to_gdal()),
                    srs="EPSG:32755", nodata=-999.0)
        cache = SceneCache()
        full = cache.get(g, stride=1.0)
        assert full.width == 512
        ovr = cache.get(g, stride=4.5)
        assert ovr.width == 128
        assert ovr.gt.dx == pytest.approx(30.0 * 4)
        # distinct cache entries, each reusable
        assert cache.get(g, stride=4.5).serial == ovr.serial
        assert cache.get(g, stride=1.0).serial == full.serial

    def test_scene_cache_big_scene_cacheable_zoomed_out(self, tmp_path):
        """A scene over the budget's share for one scene becomes
        cacheable at a coarse level."""
        from gsky_tpu.pipeline.scene_cache import SceneCache
        from gsky_tpu.pipeline.types import Granule

        p, data = self._with_ovr(tmp_path, shape=(512, 512))
        gt = GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0, -30.0)
        g = Granule(path=p, ds_name=p, namespace="b1",
                    base_namespace="b1", band=1, time_index=None,
                    timestamp=0.0, geo_transform=list(gt.to_gdal()),
                    srs="EPSG:32755", nodata=-999.0)
        cache = SceneCache(max_bytes=8 * 300 * 300 * 4)
        assert cache.max_scene_px == 300 * 300
        assert cache.get(g, stride=1.0) is None      # 512^2 too big
        ovr = cache.get(g, stride=4.0)               # 128^2 fits
        assert ovr is not None and ovr.width == 128


class TestCorruptFileRobustness:
    """Corrupt headers must produce error records, never crashes or
    uninterruptible giant allocations (fp.read/decompress/np.zeros all
    pre-allocate whatever a corrupt header declares — a fuzz run
    found multi-GB stalls before the size bounds existed)."""

    def test_corrupted_files_always_return_records(self, tmp_path):
        import random
        import time as _time

        from gsky_tpu.index.crawler import extract
        from gsky_tpu.io.netcdf import write_netcdf3

        gt = GeoTransform(0.0, 1.0, 0.0, 0.0, 0.0, -1.0)
        t_path = str(tmp_path / "a_20200110.tif")
        write_geotiff(t_path, np.ones((64, 64), np.int16), gt,
                      parse_crs("EPSG:32755"))
        n_path = str(tmp_path / "b_20200110.nc")
        write_netcdf3(n_path, {"v": np.ones((32, 32), np.float32)},
                      np.arange(32.0), np.arange(32.0), EPSG4326)
        rng = random.Random(3)
        for src in (t_path, n_path):
            raw = open(src, "rb").read()
            for trial in range(60):
                data = bytearray(raw)
                mode = trial % 3
                if mode == 0:
                    data = data[:rng.randrange(1, len(raw))]
                elif mode == 1:
                    for _ in range(rng.randrange(1, 8)):
                        i = rng.randrange(len(data))
                        data[i] ^= 1 << rng.randrange(8)
                else:
                    i = rng.randrange(len(data))
                    data[i:i + 16] = bytes(rng.randrange(256)
                                           for _ in range(16))
                p = str(tmp_path / f"f{trial}{src[-4:]}")
                open(p, "wb").write(bytes(data))
                t0 = _time.time()
                rec = extract(p)
                assert isinstance(rec, dict)
                assert _time.time() - t0 < 10.0

    def test_declared_oversize_bounds(self, tmp_path):
        from gsky_tpu.io.netcdf import NetCDF, write_netcdf3

        # a tag/dim declaring bytes beyond the file must raise cleanly
        p = str(tmp_path / "t.tif")
        gt = GeoTransform(0.0, 1.0, 0.0, 0.0, 0.0, -1.0)
        write_geotiff(p, np.ones((16, 16), np.int16), gt,
                      parse_crs("EPSG:32755"))
        with GeoTIFF(p) as g:
            # block read beyond the file: must raise, not pre-allocate
            with pytest.raises(ValueError, match="beyond file size"):
                g._decode_block(0, 1 << 40, 1, 1, 16, 16, 1,
                                np.dtype("<i2"))
            # block whose decode buffer would be multi-GB: same
            with pytest.raises(ValueError, match="declares"):
                g._decode_block(0, 16, 1, 1, 1 << 20, 1 << 12, 1,
                                np.dtype("<i2"))


class TestRangedWindowEdges:
    """Window math at granule edges, plain vs ranged-source reads
    (docs/INGEST.md): both legs share decode/assembly, so any divergence
    here is a chunk-map bug, not a codec bug."""

    def _tif(self, tmp_path, shape=(150, 130), tile_size=64):
        p = str(tmp_path / "edge.tif")
        rng = np.random.default_rng(21)
        data = rng.integers(-2000, 2000, shape).astype(np.int16)
        gt = GeoTransform(0.0, 1.0, 0.0, 0.0, 0.0, -1.0)
        write_geotiff(p, data, gt, EPSG4326, tile_size=tile_size)
        return p, data

    def test_window_clipped_to_last_partial_tile(self, tmp_path):
        from gsky_tpu.ingest.source import LocalFileSource
        p, data = self._tif(tmp_path)          # 150x130: ragged 64-px grid
        src = LocalFileSource(p)
        with GeoTIFF(p) as g:
            # the bottom-right partial tile (rows 128.., cols 128..)
            for win in [(128, 128, 2, 22), (120, 140, 10, 10),
                        (0, 149, 130, 1), (129, 0, 1, 150)]:
                a = g.read(1, win)
                b = g.read(1, win, source=src)
                np.testing.assert_array_equal(a, b)
                c0, r0, w, h = win
                np.testing.assert_array_equal(
                    a, data[r0:r0 + h, c0:c0 + w])
        src.close()

    def test_chunk_boundary_straddle_touches_two_chunks(self, tmp_path):
        from gsky_tpu.ingest.source import LocalFileSource
        p, data = self._tif(tmp_path)
        src = LocalFileSource(p)
        with GeoTIFF(p) as g:
            cm = g.chunk_map()
            # 2x2 window straddling both tile axes at (64, 64)
            assert len(cm.ranges_for((63, 63, 2, 2))) == 4
            a = g.read(1, (63, 63, 2, 2), source=src)
            np.testing.assert_array_equal(a, data[63:65, 63:65])
        src.close()

    def test_window_validation_unchanged_with_source(self, tmp_path):
        from gsky_tpu.ingest.source import LocalFileSource
        p, _ = self._tif(tmp_path)
        src = LocalFileSource(p)
        with GeoTIFF(p) as g:
            with pytest.raises(ValueError):
                g.read(1, (120, 0, 20, 10), source=src)  # past right edge
            with pytest.raises(ValueError):
                g.read(1, (-1, 0, 5, 5), source=src)
        src.close()

    def test_nc3_edge_rows(self, tmp_path):
        from gsky_tpu.ingest.source import LocalFileSource
        p = str(tmp_path / "edge.nc")
        rng = np.random.default_rng(22)
        data = rng.normal(size=(2, 33, 47)).astype(np.float32)
        write_netcdf3(p, {"v": data}, np.arange(47.0), np.arange(33.0),
                      EPSG4326, times=np.array([0.0, 1.0]))
        src = LocalFileSource(p)
        with NetCDF(p) as nc:
            for win in [(46, 32, 1, 1), (0, 32, 47, 1), (46, 0, 1, 33)]:
                a = nc.read_slice("v", 1, win)
                b = nc.read_slice_source("v", src, 1, win)
                np.testing.assert_array_equal(a, b)
            with pytest.raises(ValueError):
                nc.read_slice_source("v", src, 1, (40, 30, 10, 10))
        src.close()
