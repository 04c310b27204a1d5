"""PNG/JPEG encoding of rendered tiles.

Parity with `utils/ogc_encoders.go:82-142` (EncodePNG): 1-band byte
rasters are encoded as paletted PNG with index 0xFF transparent; 3 bands
become RGB with 0xFF-in-all-bands transparent; 4 bands RGBA.  PIL supplies
the (C-accelerated) codec.
"""

from __future__ import annotations

import asyncio
import contextvars
import io
import os
import struct
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np
from PIL import Image

from ..obs import span as obs_span
from ..obs.metrics import ENCODE_SECONDS

NODATA_BYTE = 255

# -- sized encode pool -------------------------------------------------------
# PNG/JPEG encode is pure-CPU PIL work that used to run INLINE in the
# async GetMap handler, stalling the event loop for the encode of every
# tile.  The staged tile path runs encodes here instead: a bounded pool
# (GSKY_PNG_ENCODE_WORKERS) so concurrent requests' encodes overlap
# each other and the next request's device readback, without unbounded
# thread growth under burst load.

_POOL_ENV = "GSKY_PNG_ENCODE_WORKERS"
_pool: Optional[ThreadPoolExecutor] = None
_pool_lock = threading.Lock()
_pool_stats: Dict = {"workers": 0, "pending": 0, "queue_max": 0,
                     "encoded": 0, "errors": 0, "busy_s": 0.0}


def _pool_workers() -> int:
    try:
        v = int(os.environ.get(_POOL_ENV, 4))
    except ValueError:
        return 4
    return max(1, min(32, v))


def encode_pool() -> ThreadPoolExecutor:
    global _pool
    if _pool is None:
        with _pool_lock:
            if _pool is None:
                n = _pool_workers()
                _pool_stats["workers"] = n
                _pool = ThreadPoolExecutor(
                    max_workers=n, thread_name_prefix="gsky-png")
    return _pool


def encode_pool_stats() -> Dict:
    with _pool_lock:
        out = dict(_pool_stats)
    out["busy_s"] = round(out["busy_s"], 6)
    return out


def reset_encode_pool() -> None:
    """Shut the pool down so the next encode re-reads the sizing knob
    (tests; a serving process keeps one pool for its lifetime)."""
    global _pool
    with _pool_lock:
        pool, _pool = _pool, None
        for k, v in (("workers", 0), ("pending", 0), ("queue_max", 0),
                     ("encoded", 0), ("errors", 0), ("busy_s", 0.0)):
            _pool_stats[k] = v
    if pool is not None:
        pool.shutdown(wait=False)


async def encode_async(fn, *args, spans: Optional[Dict] = None, **kw):
    """Run one encode callable on the sized pool, awaitable from the
    event loop.  Exceptions propagate to the awaiting handler exactly
    as they would inline.  ``spans`` (the staged tile path's
    per-request record) gets ``encode_s``, ``encode_cpu_s`` (the job
    thread's CPU) and the observed ``encode_queue_max`` occupancy
    folded in.  The `encode` span carries the same CPU as ``cpu_s``
    and its wall less that CPU as ``wait_s``, as does
    ``gsky_encode_seconds{phase}``."""
    loop = asyncio.get_running_loop()
    pool = encode_pool()
    with _pool_lock:
        _pool_stats["pending"] += 1
        occupancy = _pool_stats["pending"]
        if occupancy > _pool_stats["queue_max"]:
            _pool_stats["queue_max"] = occupancy
    if spans is not None:
        spans["encode_queue_max"] = max(
            spans.get("encode_queue_max", 0), occupancy)
    t0 = time.perf_counter()
    # pool threads start from an empty contextvars.Context; carry the
    # caller's (trace context included) across the hop explicitly
    ctx = contextvars.copy_context()
    cpu = [0.0]

    def _job():
        # inside the copied context so current_token() resolves: a
        # request cancelled while its encode queued gives its pool
        # slot back without burning CPU on bytes nobody will read
        from ..resilience import check_cancel
        check_cancel("encode")
        return fn(*args, **kw)

    def run():
        # the job's wall (the pool's busy seconds) and its thread's CPU
        t1, c1 = time.perf_counter(), time.thread_time()
        try:
            return ctx.run(_job)
        finally:
            cpu[0] = time.thread_time() - c1
            busy = time.perf_counter() - t1
            with _pool_lock:
                _pool_stats["busy_s"] += busy

    ok = False
    try:
        with obs_span("encode") as esp:
            out = await loop.run_in_executor(pool, run)
            # the rest of the encode's wall: the queue for a pool
            # thread, the GIL, the hop back to the event loop
            wait_s = max(0.0, time.perf_counter() - t0 - cpu[0])
            esp.set(cpu_s=round(cpu[0], 6), wait_s=round(wait_s, 6))
            try:
                ENCODE_SECONDS.labels(phase="cpu").observe(cpu[0])
                ENCODE_SECONDS.labels(phase="wait").observe(wait_s)
            except Exception:  # telemetry only - never fail the encode
                pass
        ok = True
        return out
    finally:
        # finally (not except Exception): a cancelled await must still
        # release its pending slot or the occupancy telemetry leaks
        with _pool_lock:
            _pool_stats["pending"] -= 1
            _pool_stats["encoded" if ok else "errors"] += 1
        if ok and spans is not None:
            spans["encode_s"] = spans.get("encode_s", 0.0) \
                + time.perf_counter() - t0
            spans["encode_cpu_s"] = spans.get("encode_cpu_s", 0.0) + cpu[0]

# zlib level 1 default: on satellite composites levels 6-9 buy ~10%
# smaller tiles for >2x the encode time, and the encode sits on the
# per-tile critical path.  Operators serving over thin links can trade
# CPU for bytes via GSKY_PNG_LEVEL or per-layer `png_compress_level`.
_LEVEL_ENV = "GSKY_PNG_LEVEL"
_DEFAULT_LEVEL = 1


def _resolve_level(level: Optional[int]) -> int:
    """Effective zlib level: explicit per-call (layer config) beats the
    GSKY_PNG_LEVEL env beats the level-1 default; anything outside 0-9
    is a configuration error, not a clamp."""
    if level is None:
        env = os.environ.get(_LEVEL_ENV)
        if env is None or env == "":
            return _DEFAULT_LEVEL
        try:
            level = int(env)
        except ValueError:
            raise ValueError(
                f"{_LEVEL_ENV} must be an integer 0-9, got {env!r}")
    level = int(level)
    if not 0 <= level <= 9:
        raise ValueError(
            f"PNG compress level must be 0-9, got {level}")
    return level


def encode_png(bands: Sequence[np.ndarray],
               palette: Optional[np.ndarray] = None,
               compress_level: Optional[int] = None) -> bytes:
    """bands: list of (H, W) uint8 arrays (1, 3 or 4 of them);
    palette: (256, 4) uint8 RGBA LUT for the 1-band case;
    compress_level: zlib 0-9 (None -> GSKY_PNG_LEVEL -> 1)."""
    level = _resolve_level(compress_level)
    if len(bands) == 1:
        img = Image.fromarray(bands[0], "P")
        if palette is None:
            # greyscale ramp with transparent nodata
            lut = np.stack([np.arange(256)] * 3 + [np.full(256, 255)], 1)
            lut = lut.astype(np.uint8)
            lut[NODATA_BYTE] = (0, 0, 0, 0)
        else:
            lut = np.asarray(palette, np.uint8)
            if lut.shape != (256, 4):
                raise ValueError("palette must be (256,4) RGBA")
        img.putpalette(lut[:, :3].reshape(-1).tobytes(), "RGB")
        img.info["transparency"] = bytes(lut[:, 3].tolist())
        buf = io.BytesIO()
        img.save(buf, "PNG", transparency=bytes(lut[:, 3].tolist()),
                 compress_level=level)
        return buf.getvalue()
    if len(bands) == 3:
        h, w = bands[0].shape
        rgba = np.zeros((h, w, 4), np.uint8)
        for i in range(3):
            rgba[..., i] = bands[i]
        nodata = (bands[0] == NODATA_BYTE) & (bands[1] == NODATA_BYTE) \
            & (bands[2] == NODATA_BYTE)
        rgba[..., 3] = np.where(nodata, 0, 255)
        img = Image.fromarray(rgba, "RGBA")
        buf = io.BytesIO()
        img.save(buf, "PNG", compress_level=level)
        return buf.getvalue()
    if len(bands) == 4:
        h, w = bands[0].shape
        rgba = np.stack(bands, axis=-1)
        img = Image.fromarray(rgba, "RGBA")
        buf = io.BytesIO()
        img.save(buf, "PNG", compress_level=level)
        return buf.getvalue()
    raise ValueError(f"cannot encode {len(bands)} bands as PNG")


def encode_rgba_png(rgba: np.ndarray,
                    compress_level: Optional[int] = None) -> bytes:
    """(H, W, 4) uint8 -> PNG bytes (the device palette / packed-RGB
    path output — already interleaved, no host assembly pass)."""
    buf = io.BytesIO()
    Image.fromarray(np.asarray(rgba, np.uint8), "RGBA").save(
        buf, "PNG", compress_level=_resolve_level(compress_level))
    return buf.getvalue()


def encode_jpeg(bands: Sequence[np.ndarray], quality: int = 85) -> bytes:
    """3-band JPEG (the tile_jpg_enc.go analogue)."""
    if len(bands) == 1:
        img = Image.fromarray(bands[0], "L")
    elif len(bands) == 3:
        img = Image.fromarray(np.stack(bands, axis=-1), "RGB")
    else:
        raise ValueError(f"cannot encode {len(bands)} bands as JPEG")
    buf = io.BytesIO()
    img.save(buf, "JPEG", quality=quality)
    return buf.getvalue()


def decode_png(data: bytes) -> np.ndarray:
    """PNG bytes -> (H, W, 4) uint8 (used by tests and the empty-tile
    resizer `utils/empty_tile.go:14`)."""
    img = Image.open(io.BytesIO(data)).convert("RGBA")
    return np.asarray(img)


# -- APNG assembly -----------------------------------------------------------
# The temporal wave path (docs/PERF.md "Temporal waves") renders every
# animation frame to ordinary PNG bytes on the encode pool, then splices
# the frames into one Animated PNG container.  Assembly is pure chunk
# surgery — no pixel decode, no re-compression — so frame 0's IDAT
# stream rides VERBATIM: the animation's first frame and the equivalent
# single-timestep GetMap are the same compressed bytes.

_PNG_SIG = b"\x89PNG\r\n\x1a\n"


def _png_chunks(data: bytes) -> Iterator[Tuple[bytes, bytes]]:
    """Iterate (type, payload) over one PNG byte stream."""
    if data[:8] != _PNG_SIG:
        raise ValueError("not a PNG stream")
    off = 8
    n = len(data)
    while off + 12 <= n:
        ln = struct.unpack(">I", data[off:off + 4])[0]
        typ = data[off + 4:off + 8]
        yield typ, data[off + 8:off + 8 + ln]
        off += 12 + ln


def _png_chunk(typ: bytes, payload: bytes) -> bytes:
    return (struct.pack(">I", len(payload)) + typ + payload
            + struct.pack(">I", zlib.crc32(typ + payload) & 0xFFFFFFFF))


class ApngAssembler:
    """Incremental APNG container builder over pre-encoded PNG frames.

    ``frame(png)`` returns the wire bytes for that frame — the caller
    (the OWS animation handler) streams them as each frame's encode
    completes, so the client sees frame 0 while later timesteps are
    still on the device.  Frame 0 contributes the header: its IHDR,
    palette and transparency chunks verbatim, plus the ``acTL``
    animation control chunk; every frame gets an ``fcTL`` (full-frame,
    no blending — each timestep replaces the last) and its IDAT data
    (re-typed ``fdAT`` after frame 0).  All frames must share frame
    0's geometry and palette — true by construction for one GetMap
    sequence.  ``trailer()`` closes the stream."""

    def __init__(self, num_frames: int, delay_ms: int = 500,
                 num_plays: int = 0):
        if num_frames < 1:
            raise ValueError("APNG needs at least one frame")
        self.num_frames = int(num_frames)
        self.delay_ms = max(1, min(65535, int(delay_ms)))
        self.num_plays = int(num_plays)
        self._seq = 0
        self._n = 0
        self._w = 0
        self._h = 0

    def _next_seq(self) -> int:
        s = self._seq
        self._seq += 1
        return s

    def _fctl(self) -> bytes:
        # full-canvas frame at (0,0), dispose none, blend source
        return _png_chunk(b"fcTL", struct.pack(
            ">IIIIIHHBB", self._next_seq(), self._w, self._h, 0, 0,
            self.delay_ms, 1000, 0, 0))

    def frame(self, png: bytes) -> bytes:
        """Splice one encoded PNG in; returns its container bytes."""
        if self._n >= self.num_frames:
            raise ValueError("more frames than declared in acTL")
        head: List[Tuple[bytes, bytes]] = []
        idats: List[bytes] = []
        for typ, payload in _png_chunks(png):
            if typ == b"IDAT":
                idats.append(payload)
            elif typ != b"IEND" and not idats:
                head.append((typ, payload))
        if not idats or not head or head[0][0] != b"IHDR":
            raise ValueError("malformed PNG frame")
        parts: List[bytes] = []
        if self._n == 0:
            ihdr = head[0][1]
            self._w = struct.unpack(">I", ihdr[0:4])[0]
            self._h = struct.unpack(">I", ihdr[4:8])[0]
            parts.append(_PNG_SIG)
            parts.append(_png_chunk(b"IHDR", ihdr))
            # acTL must precede the first IDAT; right after IHDR keeps
            # the frame's own ancillary chunk order untouched
            parts.append(_png_chunk(b"acTL", struct.pack(
                ">II", self.num_frames, self.num_plays)))
            for typ, payload in head[1:]:
                parts.append(_png_chunk(typ, payload))
            parts.append(self._fctl())
            for payload in idats:
                parts.append(_png_chunk(b"IDAT", payload))
        else:
            parts.append(self._fctl())
            for payload in idats:
                parts.append(_png_chunk(
                    b"fdAT",
                    struct.pack(">I", self._next_seq()) + payload))
        self._n += 1
        return b"".join(parts)

    def trailer(self) -> bytes:
        if self._n != self.num_frames:
            raise ValueError(
                f"assembled {self._n} of {self.num_frames} frames")
        return _png_chunk(b"IEND", b"")


def encode_apng(frames: Sequence[bytes], delay_ms: int = 500,
                num_plays: int = 0) -> bytes:
    """Whole-container convenience over `ApngAssembler` (tests/bench;
    the server streams per-frame instead)."""
    asm = ApngAssembler(len(frames), delay_ms, num_plays)
    return b"".join([asm.frame(f) for f in frames] + [asm.trailer()])


def empty_tile_png(width: int, height: int,
                   tile_image: Optional[bytes] = None,
                   compress_level: Optional[int] = None) -> bytes:
    """Transparent (or tiled-image) PNG of the requested size — the
    zoom-limit / error tile of `utils/empty_tile.go:14-53`."""
    canvas = Image.new("RGBA", (width, height), (0, 0, 0, 0))
    if tile_image:
        tile = Image.open(io.BytesIO(tile_image)).convert("RGBA")
        for x in range(0, width, tile.width):
            for y in range(0, height, tile.height):
                canvas.paste(tile, (x, y))
    buf = io.BytesIO()
    canvas.save(buf, "PNG", compress_level=_resolve_level(compress_level))
    return buf.getvalue()
