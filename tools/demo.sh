#!/usr/bin/env bash
# Single-node demo stack — the `docker/gsky_entry_point.sh` equivalent:
# builds the native codec, synthesises a sample Landsat-style archive,
# crawls + ingests it into a MAS instance, then launches
#   gsky-mas   (metadata index HTTP API)     on :8888
#   gsky-rpc   (TPU compute worker, gRPC)    on :11429
#   gsky-ows   (OGC WMS/WCS/WPS/DAP4 server) on :8080
# and smoke-checks a GetMap tile.  Ctrl-C tears everything down.
#
# One process holds the chip: in this split topology it is gsky-rpc.
# gsky-ows is told JAX_PLATFORMS=cpu (its own JAX work runs on the CPU on
# purpose), gsky-mas and the crawler never initialise JAX, and the
# archive generator below is told the CPU too.  Run the whole script
# under JAX_PLATFORMS=cpu to keep gsky-rpc off the chip as well.
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
DEMO="${DEMO_DIR:-$(mktemp -d /tmp/gsky_demo.XXXXXX)}"
PY="${PYTHON:-python}"
cd "$ROOT"

echo "[demo] building native codec"
make -C gsky_tpu/native >/dev/null

echo "[demo] generating sample archive under $DEMO"
JAX_PLATFORMS=cpu $PY - "$DEMO" <<'EOF'
import json, os, sys
sys.path.insert(0, os.getcwd())
from tools import sample_archive
demo = sys.argv[1]
data = os.path.join(demo, "data"); os.makedirs(data, exist_ok=True)
store, utm, paths = sample_archive.build_archive(data)
conf = os.path.join(demo, "conf"); os.makedirs(conf, exist_ok=True)
with open(os.path.join(conf, "config.json"), "w") as fp:
    json.dump({
        "service_config": {"ows_hostname": "localhost:8080",
                           "mas_address": "127.0.0.1:8888",
                           "worker_nodes": ["127.0.0.1:11429"]},
        "layers": [{
            "name": "landsat", "title": "Synthetic Landsat mosaic",
            "data_source": data,
            "rgb_products": [f"LC08_20200{110+k}_T1"
                             for k in range(sample_archive.N_SCENES)],
            "time_generator": "mas",
            "palette": {"interpolate": True, "colours": [
                {"R": 0, "G": 0, "B": 120, "A": 255},
                {"R": 250, "G": 250, "B": 90, "A": 255}]},
        }],
        "processes": [{
            "identifier": "geometryDrill", "title": "Geometry drill",
            "max_area": 100000,
            "data_sources": [{"data_source": data,
                              "rgb_products": ["LC08_20200110_T1"]}],
            "approx": False}],
    }, fp, indent=2)
print(data)
EOF

echo "[demo] crawling archive -> MAS ingest TSV"
$PY -m gsky_tpu.index.crawler -fmt tsv "$DEMO/data" > "$DEMO/crawl.tsv"

cleanup() { kill 0 2>/dev/null || true; }
trap cleanup EXIT INT TERM

echo "[demo] starting gsky-mas :8888"
$PY -m gsky_tpu.index.api -port 8888 -ingest "$DEMO/crawl.tsv" &
sleep 1

echo "[demo] starting gsky-rpc :11429 (holds the chip)"
$PY -m gsky_tpu.worker.server -p 11429 &
sleep 2

echo "[demo] starting gsky-ows :8080 on the CPU (conf $DEMO/conf)"
JAX_PLATFORMS=cpu $PY -m gsky_tpu.server.main -port 8080 -conf "$DEMO/conf" -static "$ROOT/static" &
sleep 3

echo "[demo] waiting for gsky-ows to come up"
for i in $(seq 1 60); do
    if curl -sf "http://127.0.0.1:8080/ows?service=WMS&request=GetCapabilities" >/dev/null 2>&1; then
        break
    fi
    sleep 1
done

echo "[demo] smoke: GetCapabilities + GetMap"
if curl -sf "http://127.0.0.1:8080/ows?service=WMS&request=GetCapabilities" \
        | head -c 200 >/dev/null; then
    echo "[demo]   capabilities OK"
else
    echo "[demo]   capabilities FAILED"
fi
if curl -sf "http://127.0.0.1:8080/ows?service=WMS&request=GetMap&version=1.3.0&layers=landsat&crs=EPSG:3857&bbox=16478548,-4211230,16489679,-4198025&width=256&height=256&format=image/png&time=2020-01-10T00:00:00.000Z" \
        -o "$DEMO/tile.png"; then
    echo "[demo]   GetMap OK -> $DEMO/tile.png"
else
    echo "[demo]   GetMap FAILED"
fi

echo "[demo] stack is up:"
echo "  WMS:  http://localhost:8080/ows?service=WMS&request=GetCapabilities"
echo "  WCS:  http://localhost:8080/ows?service=WCS&request=GetCapabilities"
echo "  WPS:  http://localhost:8080/ows?service=WPS&request=GetCapabilities"
echo "  MAS:  http://localhost:8888/"
echo "[demo] Ctrl-C to stop"
wait
