"""CLI black-box tests — the role of the reference's bats suites
(`testsuite/api.bats`, `crawl.bats`, `grpc-server.bats`): every binary's
flags, usage errors and exit codes, exercised through the real argv
entry points in subprocesses (the same `python -m`/console-script
surface an operator gets)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(module, *args, timeout=120):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # the one way to ask for the CPU
    return subprocess.run(
        [sys.executable, "-m", module, *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env=env)


class TestOwsCli:
    def _conf(self, tmp_path, layers=None):
        conf = tmp_path / "conf"
        conf.mkdir()
        (conf / "config.json").write_text(json.dumps({
            "service_config": {"ows_hostname": "", "mas_address": ""},
            "layers": layers if layers is not None else [
                {"name": "l1", "title": "t", "data_source": "/tmp",
                 "rgb_products": ["b"], "time_generator": "mas"}],
        }))
        return str(conf)

    def test_check_conf_ok(self, tmp_path):
        r = run_cli("gsky_tpu.server.main", "-conf",
                    self._conf(tmp_path), "-check_conf")
        assert r.returncode == 0, r.stderr
        assert "OK" in r.stdout and "1 layer" in r.stdout

    def test_check_conf_bad_json(self, tmp_path):
        conf = tmp_path / "conf"
        conf.mkdir()
        (conf / "config.json").write_text("{not json")
        r = run_cli("gsky_tpu.server.main", "-conf", str(conf),
                    "-check_conf")
        assert r.returncode == 1
        assert "configuration error" in r.stderr

    def test_check_conf_missing_dir(self, tmp_path):
        r = run_cli("gsky_tpu.server.main", "-conf",
                    str(tmp_path / "nope"), "-check_conf")
        assert r.returncode == 1

    def test_dump_conf_prints_namespaces(self, tmp_path):
        r = run_cli("gsky_tpu.server.main", "-conf",
                    self._conf(tmp_path), "-dump_conf")
        assert r.returncode == 0, r.stderr
        assert "== namespace" in r.stdout
        assert '"layers"' in r.stdout and '"l1"' in r.stdout

    def test_unknown_flag_usage_exit(self, tmp_path):
        r = run_cli("gsky_tpu.server.main", "--no-such-flag")
        assert r.returncode == 2          # argparse usage error
        assert "usage" in r.stderr.lower()


class TestCrawlCli:
    def test_no_args_exits_nonzero(self):
        r = run_cli("gsky_tpu.index.crawler")
        assert r.returncode != 0

    def test_crawls_file_to_json(self, tmp_path):
        from gsky_tpu.geo.crs import parse_crs
        from gsky_tpu.geo.transform import GeoTransform
        from gsky_tpu.io import write_geotiff

        p = str(tmp_path / "t_20200110.tif")
        write_geotiff(p, np.ones((16, 16), np.int16),
                      GeoTransform(590000.0, 30.0, 0.0, 6105000.0, 0.0,
                                   -30.0),
                      parse_crs("EPSG:32755"), nodata=-1)
        r = run_cli("gsky_tpu.index.crawler", p, "-fmt", "json")
        assert r.returncode == 0, r.stderr
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        assert rec["file_type"] == "GeoTIFF"
        assert rec["geo_metadata"][0]["timestamps"]

    def test_tsv_default_format(self, tmp_path):
        from gsky_tpu.geo.crs import parse_crs
        from gsky_tpu.geo.transform import GeoTransform
        from gsky_tpu.io import write_geotiff

        p = str(tmp_path / "t_20200110.tif")
        write_geotiff(p, np.ones((8, 8), np.float32),
                      GeoTransform(0, 1, 0, 0, 0, -1),
                      parse_crs("EPSG:4326"))
        r = run_cli("gsky_tpu.index.crawler", p)
        assert r.returncode == 0, r.stderr
        line = r.stdout.strip().splitlines()[-1]
        # path \t gdal \t json — crawl_pipeline.sh's TSV contract
        fields = line.split("\t")
        assert fields[0] == p and fields[1] == "gdal"
        assert json.loads(fields[2])["file_type"] == "GeoTIFF"


class TestMasCli:
    def test_missing_ingest_file_fails(self):
        r = run_cli("gsky_tpu.index.api", "-ingest", "/no/such/file")
        assert r.returncode != 0

    def test_unknown_flag(self):
        r = run_cli("gsky_tpu.index.api", "--bogus")
        assert r.returncode == 2


class TestRpcCli:
    def test_unknown_flag(self):
        r = run_cli("gsky_tpu.worker.server", "--bogus")
        assert r.returncode == 2
