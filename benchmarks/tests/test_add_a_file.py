"""Driven by data: a configuration, a traffic mix and a per-layer metric
are added as new files plus entries in BENCHMARK.json, with no edit to
any file the benchmark already has, and the new cell runs."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_a_new_cell_is_new_files_only(tmp_path):
    # a copy of the benchmark beside the program it drives
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "gsky_tpu"), tmp_path / "gsky_tpu")
    before = {p: open(p, "rb").read()
              for d, _, fs in os.walk(tmp_path / "benchmarks")
              for p in (os.path.join(d, f) for f in fs)}

    # a configuration: the MODIS stack with one band and a layer of its own
    config = json.load(open(
        tmp_path / "benchmarks/configs/modis-fc-drill.json"))
    config["name"] = "throwaway"
    config["archive"]["variables"] = ["phot_veg"]
    config["layers"] = config["layers"][:1]
    config["processes"] = []
    with open(tmp_path / "benchmarks/configs/throwaway.json", "w") as fp:
        json.dump(config, fp)
    # a traffic mix: parameters for the generator that is there, here
    # map sessions over the NetCDF stack instead of the GeoTIFF scenes
    mix = {"generator": "xyz_sessions",
           "loop": {"kind": "closed", "connections": 3},
           "layers": {"phot_veg": 1.0},
           "zoom_shares": {"11": 0.3, "12": 0.4, "13": 0.3},
           "viewport": {"cols": [3, 4], "rows": [2, 2]}, "views": [4, 8],
           "step": {"pan": 0.7, "zoom": 0.3}, "pan_tiles": [1, 1],
           "warmup": {"head_margin": 1.75, "head_per_s": 10,
                      "pass_requests": 50, "twin_seconds_max": 60},
           "check": {"tiles": 8, "bound_mismatch": 0.01}}
    with open(tmp_path / "benchmarks/traffic/throwaway-mix.json", "w") as fp:
        json.dump(mix, fp)
    # a per-layer metric: a reader of its own
    with open(tmp_path / "benchmarks/layer_metrics/throwaway.granules.py",
              "w") as fp:
        fp.write('"""Granules per rendered tile."""\n\n\ndef read(ctx):\n'
                 '    return ctx.ratio(["tile_stages.granules"],\n'
                 '                     ["tile_stages.tiles"])\n')
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["configs"].append({
        "name": "throwaway", "source": "test", "reduced": [],
        "file": "benchmarks/configs/throwaway.json", "why": "test"})
    bench["workloads"].append({
        "name": "throwaway.cell", "config": "throwaway",
        "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "throwaway.granules", "unit": "granules", "better": "lower",
        "source": "program_counter", "layer": "index",
        "moves": "latency_p50_ms", "workloads": ["throwaway.cell"]})
    # a metric that lists its cells gets the new one added to the list
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("throwaway.cell")
    with open(tmp_path / "BENCHMARK.json", "w") as fp:
        json.dump(bench, fp)

    def run(trace):
        out = subprocess.run(
            [sys.executable, str(tmp_path / "benchmarks/run.py"),
             "--workload", "throwaway.cell", "--seed", "4", "--seconds", "3",
             "--trace", str(trace), "--rehearsal",
             "--out", str(tmp_path / "out")],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    line = run(0)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "problems", "checks"]
    assert line["problems"] == []
    assert line["correct"] and line["attempted"] > 0 and line["failed"] == 0
    # the 95th percentile is reported from 200 requests up
    assert {"latency_p50_ms", "throughput_rps", "setup_s"} \
        <= set(line["metrics"]) <= {"latency_p50_ms", "latency_p95_ms",
                                    "throughput_rps", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    traced = run(1)
    assert traced["metrics"]["throwaway.granules"]["value"] == 1.0
    # a CPU run carries no device metric
    assert "device.idle_share" not in traced["metrics"]
    assert "busy_s" not in traced["device"] and "breakdown" not in traced

    after = {p: open(p, "rb").read()
             for d, _, fs in os.walk(tmp_path / "benchmarks")
             for p in (os.path.join(d, f) for f in fs)
             if "__pycache__" not in p}
    assert all(after[p] == body for p, body in before.items())


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` the command exits non-zero and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks/run.py"), "--workload",
         "landsat8-mosaic.pan-cold", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"})
    assert out.returncode != 0
    assert not out.stdout.strip().startswith("{")


def test_cpu_is_for_rehearsals_only():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks/run.py"),
         "--workload", "landsat8-mosaic.pan-cold", "--seed", "1",
         "--seconds", "1", "--trace", "0"], capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 2 and not out.stdout.strip()
