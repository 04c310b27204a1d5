"""Persistent kernel race ledger: durable pallas-vs-XLA verdicts.

`run_with_fallback` (ops/pallas_tpu.py) races each pallas kernel against
its XLA fallback once per (kernel, shape-bucket token) and demotes clear
losers — but that state was per-process, so every worker re-paid the
race (the r5 warm-drill 1.45 s outlier vs 4.7 ms XLA was exactly this
cost).  This module makes the verdicts durable and process-shared:

- one JSONL file (``GSKY_KERNEL_LEDGER``, default under the metrics log
  dir when the server configures one, else the system tmp dir);
- records are appended atomically (O_APPEND, one line per verdict, kept
  under PIPE_BUF so concurrent workers never interleave);
- on load the records replay last-verdict-wins into the in-process race
  state (`pallas_tpu._SLOW` / `_PROVEN` / `_FAILED`), so a fresh worker
  skips every already-decided race;
- corrupt lines are skipped (a torn write must never poison the pipe);
- deleting the file re-races everything — the operator's reset knob.

Record schema (one JSON object per line)::

    {"kernel": "warp_scored", "token": "((8, 512, 512), ...)",
     "verdict": "promoted" | "demoted" | "failed",
     "t_pallas_ms": 1.2, "t_xla_ms": 8.0, "ts": 1754000000.0, "pid": 42}

``token`` is ``repr()`` of the bucketed sync token (plain ints/strs/
tuples only) so it round-trips through ``ast.literal_eval``.
"""

from __future__ import annotations

import ast
import json
import os
import tempfile
import threading
import time
from typing import Dict, Optional, Tuple

_ENV = "GSKY_KERNEL_LEDGER"
_DEFAULT_NAME = "gsky_kernel_ledger.jsonl"

VERDICTS = ("promoted", "demoted", "failed")

# record-schema version this process writes; loaders skip lines with a
# version they don't understand (never crash on a newer worker's file)
SCHEMA_VERSION = 1

# kernels whose tokens are VERSIONED: the token's first element must be
# this prefix for a ledger verdict to replay onto the kernel.  The paged
# kernels (ops/paged.py) introduced the scheme — their token meaning
# (page geometry + ragged pads) is disjoint from the bucketed-era
# (stack-shape, window-bucket) tokens, and a stale bucketed verdict
# replayed onto them would demote/promote the wrong program.  Bump the
# prefix (pg1 -> pg2) when a kernel's token meaning changes.
TOKEN_VERSIONS = {
    "warp_scored_paged": "pg1",
    "warp_render_paged": "pg1",
    # fused expression epilogue (ops/paged.py::render_expr_paged): the
    # token also carries the expression's structural fingerprint hash,
    # so same-structure expressions share verdicts and a normalization
    # change bumps ex1 wholesale
    "render_expr_paged": "ex1",
    # autoplan's block-shape cost model (pipeline/autoplan.py): the
    # chosen shape is encoded IN the token (verdict always "promoted"),
    # so a costed shape is decided once per process lineage and
    # replayed from the file, never re-derived
    "plan_block": "pl1",
}


def token_version_ok(kernel: str, token) -> bool:
    """True when a decoded ledger token belongs to `kernel`'s CURRENT
    token scheme: versioned kernels require the matching prefix;
    unversioned kernels reject tokens that carry any known version
    prefix (a paged verdict must not replay onto the bucketed race)."""
    want = TOKEN_VERSIONS.get(kernel)
    lead = token[0] if isinstance(token, tuple) and token else None
    if want is not None:
        return lead == want
    return not (isinstance(lead, str)
                and lead in set(TOKEN_VERSIONS.values()))

_lock = threading.Lock()
# set by the server from its metrics -log_dir; env always wins
_default_dir: Optional[str] = None


def set_default_dir(path: str) -> None:
    """Point the default ledger location at the metrics log dir (called
    by server startup; GSKY_KERNEL_LEDGER still overrides)."""
    global _default_dir
    _default_dir = path or None


def ledger_path() -> str:
    p = os.environ.get(_ENV)
    if p:
        return p
    if _default_dir:
        return os.path.join(_default_dir, _DEFAULT_NAME)
    return os.path.join(tempfile.gettempdir(), _DEFAULT_NAME)


def record(kernel: str, token, verdict: str,
           t_pallas_ms: Optional[float] = None,
           t_xla_ms: Optional[float] = None,
           reason: Optional[str] = None) -> None:
    """Append one verdict atomically.  Never raises — durability is an
    optimisation; losing a record only costs one future re-race.
    ``reason`` distinguishes a ``failed`` written because the compile
    RAISED ("compile") from other failure shapes; loaders that don't
    know the field ignore it."""
    if verdict not in VERDICTS:
        return
    try:
        doc = {"v": SCHEMA_VERSION, "kernel": str(kernel),
               "token": repr(token), "verdict": verdict,
               "ts": round(time.time(), 3), "pid": os.getpid()}
        if reason is not None:
            doc["reason"] = str(reason)
        if t_pallas_ms is not None:
            doc["t_pallas_ms"] = round(float(t_pallas_ms), 3)
        if t_xla_ms is not None:
            doc["t_xla_ms"] = round(float(t_xla_ms), 3)
        line = json.dumps(doc, separators=(",", ":")) + "\n"
        data = line.encode()
        if len(data) > 4096:    # PIPE_BUF floor: stay atomic or stay out
            return
        path = ledger_path()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with _lock:
            fd = os.open(path, os.O_APPEND | os.O_CREAT | os.O_WRONLY,
                         0o644)
            try:
                os.write(fd, data)
            finally:
                os.close(fd)
    except Exception:   # noqa: BLE001 - never fail a dispatch over IO
        pass


def entries() -> Dict[Tuple[str, str], Dict]:
    """Merged ledger: {(kernel, token_repr) -> last record}.  Corrupt or
    foreign lines are skipped; a missing file is an empty ledger."""
    out: Dict[Tuple[str, str], Dict] = {}
    try:
        with open(ledger_path(), "r", encoding="utf-8",
                  errors="replace") as fp:
            for line in fp:
                line = line.strip()
                if not line:
                    continue
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(doc, dict):
                    continue
                v = doc.get("v", 1)     # pre-versioning lines are v1
                if not isinstance(v, int) or v > SCHEMA_VERSION:
                    continue            # newer worker's schema: skip
                k = doc.get("kernel")
                t = doc.get("token")
                if not isinstance(k, str) or not isinstance(t, str) \
                        or doc.get("verdict") not in VERDICTS:
                    continue
                out[(k, t)] = doc
    except OSError:
        pass
    return out


def decode_token(token_repr: str):
    """token repr -> the original tuple (tokens are built from plain
    ints/floats/strs/tuples/None, so literal_eval round-trips them);
    None when the repr is not literal-safe."""
    try:
        return ast.literal_eval(token_repr)
    except (ValueError, SyntaxError):
        return None


def stats() -> Dict:
    """The /debug "kernels" block + the bench/probe dump: ledger path,
    per-kernel verdict counts and entries, and the in-process race
    state — in counts (``session``) and by name
    (`pallas_tpu.kernel_state`: failed / demoted / promoted /
    lowered), so a fallback is readable beside the durable verdicts."""
    path = ledger_path()
    doc: Dict = {"ledger_path": path,
                 "ledger_present": os.path.exists(path), "kernels": {}}
    for (kernel, tok), rec in sorted(entries().items()):
        k = doc["kernels"].setdefault(
            kernel, {"promoted": 0, "demoted": 0, "failed": 0,
                     "entries": []})
        k[rec["verdict"]] += 1
        k["entries"].append({
            "token": tok, "verdict": rec["verdict"],
            "reason": rec.get("reason"),
            "t_pallas_ms": rec.get("t_pallas_ms"),
            "t_xla_ms": rec.get("t_xla_ms"), "ts": rec.get("ts")})
    try:
        from . import pallas_tpu as pt
        doc["session"] = {
            "pallas_enabled": pt.use_pallas(),
            "interpret": pt.pallas_interpret(),
            "failed_kernels": sorted(pt._FAILED),
            "demoted_pairs": len(pt._SLOW),
            "proven_pairs": len(pt._PROVEN)}
        doc.update(pt.kernel_state())
    except Exception:   # observability must never fail a request
        pass
    return doc
