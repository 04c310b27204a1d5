#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the served path.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It makes the cell's archive from the seed,
boots the real `gsky-ows` with an in-process MAS, drives it over HTTP,
warms up, measures for --seconds, checks a sample of answers against
`reference.py` outside the window, and prints one JSON object as the
last line of stdout: correct, attempted, failed, metrics, device
(breakdown with --trace 1), then problems (the first three reasons why
it is not correct, empty where it is) and checks (every number compared,
beside its limit; the last lines of stderr say the same).  Everything
else worth reading goes to earlier lines and to <out>/<cell>.json.

It exits 2 and prints no result when JAX finds no TPU, or fewer chips
than the cell asks for.  `JAX_PLATFORMS=cpu` is accepted only with
--rehearsal: tiny sizes, proves the script, reports no device metric.
"""

import time

T_START = time.perf_counter()       # set-up runs from here

import argparse                     # noqa: E402
import itertools                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import subprocess                   # noqa: E402
import sys                          # noqa: E402
import tempfile                     # noqa: E402
import threading                    # noqa: E402
from concurrent.futures import ThreadPoolExecutor      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

TRACE_LEAD_S = 2.0          # into the window before the profiler starts
TRACE_MAX_S = 5.0           # the profiled slice
# Loads from the compile cache that a window may hold and stay `correct`.
# A twin now and then runs another program than its request (the twin's
# footprint falls over a bucket's edge, or touches another count of
# scenes): with twins half a pixel on, one load in 11,600 requests of
# three 40 s windows, none in 38,000 of twenty 20 s windows, and that
# run's numbers lay inside the others' (PERF.md section 6).  A load is
# what such a program costs a checkout that has run it before; one that
# has not compiles it, which nothing here bears (seed 2147484103, PR 31),
# so since then a twin lies 1/128 of a pixel on
# (`generators/xyz_sessions.py::twins`).  Past the twinned head 1-2 % of
# requests load a program; three already say so.
STRAY_LOADS = 2


def log(msg):
    print(f"[bench {time.perf_counter() - T_START:7.2f}] {msg}", flush=True)


class CompileProbe:
    """Compile requests and persistent-cache hits, from JAX's monitoring
    events in this process.  jax 0.9 fires the compile event for a cache
    hit too, so fresh compiles are requests minus hits."""

    def __init__(self):
        import jax.monitoring
        self.requests = self.hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    def snapshot(self):
        """(fresh compiles, loads from the persistent cache) so far."""
        with self._lock:
            return self.requests - self.hits, self.hits


def build_archive(cell, seed, cache):
    """The cell's archive under cache/<config>/, made from the seed, or
    the one already there if the same files made it from the same seed.
    Returns (root, crawl file)."""
    import hashlib

    from benchmarks import spec
    p = cell.config["archive"]
    mod = spec.load_kind("archives", p["kind"])
    with open(mod.__file__, "rb") as fp:
        stamp = {"seed": seed, "archive": p,
                 "generator": hashlib.sha256(fp.read()).hexdigest()}
    root = os.path.join(cache, "archive", cell.config["name"])
    manifest = os.path.join(root, "manifest.json")
    crawl = os.path.join(root, "crawl.jsonl")
    try:
        with open(manifest) as fp:
            if json.load(fp) == stamp:
                log(f"archive reused: {root}")
                return root, crawl
    except (OSError, ValueError):
        pass
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    t0 = time.perf_counter()
    recs = mod.build(p, seed, root)
    with open(crawl, "w") as fp:
        for r in recs:
            if r.get("error"):
                raise RuntimeError(f"crawl failed: {r}")
            fp.write(json.dumps(r, default=float) + "\n")
    with open(manifest, "w") as fp:
        json.dump(stamp, fp)
    log(f"archive built in {time.perf_counter() - t0:.1f} s: {root}")
    return root, crawl


def warm_up(client, gen, server, probe, w, plan, seconds):
    """Two phases, in passes so that the log shows how they went.
    Returns (passes, warmed): `warmed` is how many of the window's first
    requests have had a twin, or None where the traffic asks for none.

    Prefill (`Generator.prefill`): what has to be resident before the
    first request (a drill's stacks); its first pass is sent again until
    the counters the traffic file names stand still, or the time is up.

    Twins (`Generator.twins`, where the traffic file gives
    `head_margin`): a program's first use in a process compiles it or
    loads it from the compile cache, which stalls a dispatch slot for
    0.3 to 3 s, and the lattice of programs has a long tail (stack depth
    x two window buckets), so no draw but the window's own reaches them
    all.  So the head of the window's own sequence is taken off in
    passes, and for each request of a pass one is sent that runs the
    same program and shares nothing else with it; the window's requests
    themselves stay new to the process.  How long a head is decided by
    what the passes show and not by a count: it grows until it holds
    `head_margin` times what the window would take at the rate of the
    fastest pass so far (the pass that met the fewest new programs runs
    at the window's own speed), and never less than `head_per_s` for
    each second of window.  A program that serves faster twins faster
    and gets a longer head.  Should the window outrun the head all the
    same, `correct` says so (`program_state_problems`)."""
    from benchmarks.ctx import dig
    t0 = time.perf_counter()
    passes = []

    def send(reqs, phase):
        c0, d0 = probe.snapshot(), server.debug()
        t = time.perf_counter()
        res = client.closed(reqs, plan.connections)
        took = time.perf_counter() - t
        c1, d1 = probe.snapshot(), server.debug()
        moving = [p for p in w.get("until_still", [])
                  if dig(d1, p) != dig(d0, p)]
        bad = [r for r in res if not r.ok]
        passes.append({"phase": phase, "requests": len(res),
                       "failed": len(bad), "fresh": c1[0] - c0[0],
                       "loads": c1[1] - c0[1], "moving": moving,
                       "rps": round(len(res) / took, 1),
                       "s": round(time.perf_counter() - t0, 2)})
        if bad:
            log(f"warm-up: {len(bad)} failed, e.g. {bad[0].status} "
                f"{(bad[0].body or b'')[-200:]!r}")
        return moving

    n = w["pass_requests"]
    fill = gen.prefill()
    for i in range(0, len(fill), n):
        while send(fill[i:i + n], "prefill") and not i \
                and time.perf_counter() - t0 < w["settle_seconds"]:
            pass
    if "head_margin" not in w:
        return passes, None
    head, t_twins = [], time.perf_counter()
    need = w["head_per_s"] * seconds
    while len(head) < need \
            and time.perf_counter() - t_twins < w["twin_seconds_max"]:
        chunk = list(itertools.islice(plan.reqs, n))
        if not chunk:
            break
        send(gen.twins(chunk), "twins")
        head += chunk
        need = max(need, w["head_margin"] * passes[-1]["rps"] * seconds)
    plan.reqs = itertools.chain(head, plan.reqs)
    return passes, len(head)


class TraceSlice:
    """jax.profiler round a steady slice of the window, from a thread of
    its own; the wall-clock stamp taken at its start aligns the trace
    with the program's spans."""

    def __init__(self, out_dir, seconds):
        self.dir = os.path.join(out_dir, "profile")
        shutil.rmtree(self.dir, ignore_errors=True)
        self.length = max(0.5, min(TRACE_MAX_S, seconds - 2 * TRACE_LEAD_S))
        self.lead = min(TRACE_LEAD_S, max(0.0, (seconds - self.length) / 2))
        self.wall0 = self.traced_s = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()

    def _run(self):
        import jax
        time.sleep(self.lead)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.wall0 = time.time()
        t0 = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        time.sleep(self.length)
        self.traced_s = time.perf_counter() - t0
        jax.profiler.stop_trace()

    def finish(self):
        """The .xplane.pb, or None."""
        import glob
        self._thread.join(300)
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        return found[0] if found else None


def reduce_trace(tracer, ctx, out_dir, spans_file):
    """Fill ctx with the profiled slice (kept as <out>/<cell>.xplane.pb)
    and return the line's `breakdown`, or None where no operation ran on
    a device (a rehearsal)."""
    from benchmarks import reduce
    xplane = tracer.finish()
    ctx.traced_s = tracer.traced_s or 0.0
    if not xplane:
        return None
    kept = os.path.join(out_dir, f"{ctx.cell.name}.xplane.pb")
    shutil.copyfile(xplane, kept)
    shutil.rmtree(tracer.dir, ignore_errors=True)
    ctx.trace = reduce.load(kept)
    made = reduce.busy(ctx.trace)
    if not made:
        return None
    ctx.busy_s, intervals = made
    idle = reduce.gaps(intervals, intervals[0, 0], intervals[-1, 1])
    return {"device_ops": reduce.top_ops(ctx.trace),
            "idle_gaps": reduce.label_gaps(
                idle, reduce.read_spans(spans_file), tracer.wall0)}


def program_state(ctx, traffic):
    """What `correct` needs besides right answers, read once for the
    problems and for the line's `checks`: programs first used inside the
    window, the twinned head, kernels that failed, ran interpreted or
    failed to prewarm, the device guard's incidents, and the counters
    the traffic file wants still."""
    from benchmarks.ctx import dig
    d1 = ctx.debug1
    k = d1.get("kernels", {})
    dev = d1.get("device", {})
    fresh, loads = ctx.compiles_in_window
    return {
        "fresh": fresh, "loads": loads,
        "failed": k.get("failed") or [],
        "interpreted": [n for n, modes in (k.get("lowered") or {}).items()
                        if "interpret" in modes],
        "prewarm": d1.get("prewarm") if dig(d1, "prewarm.failures") else None,
        "guard": {w: dev.get(w) for w in ("hangs", "crashes", "ooms",
                                          "corruptions", "reinits")
                  if dev.get(w)},
        "moved": {path: ctx.delta(path)
                  for path in traffic.get("demand_still", [])
                  if ctx.delta(path)}}


def program_state_problems(ctx, st):
    """The path that served is the one the cell is about, and the window
    measured serving and nothing else (a program first used inside it
    stalls a dispatch slot, and ten such stalls once took a cell from 97
    to 36 requests/s).  `st`: `program_state`."""
    out = []
    if st["fresh"] or st["loads"] > STRAY_LOADS:
        out.append(f"{st['fresh']} program(s) compiled and {st['loads']} "
                   f"loaded inside the window (first used there: "
                   f"{ctx.first_used()}): warm-up did not reach them")
    if ctx.warmed is not None and len(ctx.results) > ctx.warmed:
        out.append(f"the window sent {len(ctx.results)} requests and only "
                   f"its first {ctx.warmed} had a twin in warm-up")
    if st["failed"]:
        out.append(f"failed kernels: {st['failed']}")
    if st["interpreted"]:
        out.append(f"kernels ran interpreted: {st['interpreted']}")
    if st["guard"]:
        out.append(f"device guard incidents: {st['guard']}")
    for path, by in st["moved"].items():
        out.append(f"{path} moved by {by} in the window")
    if st["prewarm"]:
        out.append(f"prewarm: {st['prewarm']}")
    return out


def checks_of(records, ctx, check, st):
    """The line's `checks`: every number `correct` compares, beside its
    limit where it has one of its own (the others' limit is 0), folded
    from the generator's records, the traffic file's `check` and
    `program_state`.  A drill is counted by its rows' state
    (`generators/polygons.py::_held`)."""
    rows = [r.get("rows") for r in records]
    out = {"answers_checked": sum(
        1 for r in records if {"mismatch", "max_abs_err", "rows"} & set(r))}
    if "bound_mismatch" in check:
        out["mismatch_max"] = max((r["mismatch"] for r in records
                                   if "mismatch" in r), default=0.0)
        out["mismatch_bound"] = check["bound_mismatch"]
    if "bound_abs" in check:
        out["abs_err_max"] = max((r["max_abs_err"] for r in records
                                  if "max_abs_err" in r), default=0.0)
        out["abs_err_bound"] = check["bound_abs"]
    out.update(
        served_twice_differs=sum(1 for r in records if r.get("served_twice")),
        rows_malformed=rows.count("malformed"),
        rows_empty_on_nodata=rows.count("empty_on_nodata"),
        rows_undecided=rows.count("undecided"),
        compiled_in_window=st["fresh"], loaded_in_window=st["loads"],
        loaded_in_window_bound=STRAY_LOADS, sent=len(ctx.results))
    if ctx.warmed is not None:
        out["warmed"] = ctx.warmed
    out.update(
        demand_moved=sum(abs(v) for v in st["moved"].values()),
        kernel_incidents=len(st["failed"]) + len(st["interpreted"])
        + bool(st["prewarm"]),
        guard_incidents=sum(st["guard"].values()))
    return out


def read_metrics(entries, directory, ctx):
    from benchmarks import spec
    out = {}
    for m in entries:
        value = spec.reader(directory, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the measured window (default: run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "bench"))
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on JAX_PLATFORMS=cpu: proves the "
                         "script, says nothing about the chip")
    args = ap.parse_args(argv)

    on_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if args.rehearsal != on_cpu:
        print("benchmark: platform cpu is for --rehearsal only, and "
              "--rehearsal needs JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    native_dir = os.path.join(ROOT, "gsky_tpu", "native")
    if not os.path.isdir(native_dir):
        print(f"benchmark: {native_dir} is missing; the benchmark drives "
              "the gsky_tpu checkout it sits in", file=sys.stderr)
        return 2

    from benchmarks import spec
    cell = spec.load_cell(args.workload, args.rehearsal)
    seconds = args.seconds if args.seconds is not None else \
        spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]
    os.makedirs(args.out, exist_ok=True)

    # everything a run leaves behind stays inside the checkout, at fixed
    # paths: archive, kernel ledger (so a cell's runs in one checkout
    # race a kernel once, not once per run), logs.  A rehearsal keeps
    # nothing large in the repository.
    cache = tempfile.mkdtemp(prefix="gsky_bench_") if args.rehearsal \
        else os.path.join(HERE, ".cache")
    scratch = os.path.join(cache, "run")
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    os.environ["GSKY_KERNEL_LEDGER"] = os.path.join(cache,
                                                    "kernel_ledger.jsonl")
    os.environ["GSKY_POOL_JOURNAL"] = os.path.join(scratch,
                                                   "pool_journal.jsonl")
    spans_file = os.path.join(args.out, f"{cell.name}.spans.jsonl")
    if args.trace:
        if os.path.exists(spans_file):
            os.unlink(spans_file)
        os.environ["GSKY_TRACE_FILE"] = spans_file
        os.environ["GSKY_TRACE_SAMPLE"] = "1"
    for k, v in cell.config.get("environment", {}).items():
        os.environ[k] = str(v)

    try:
        return run(args, cell, seconds, cache, scratch, spans_file,
                   native_dir)
    finally:
        if args.rehearsal:
            shutil.rmtree(cache, ignore_errors=True)


def run(args, cell, seconds, cache, scratch, spans_file, native_dir):
    import numpy as np

    from benchmarks import spec
    from benchmarks.client import Client
    from benchmarks.ctx import Ctx
    from benchmarks.serve import Server, write_config

    # built from what git commits: *.so is ignored, and without it the
    # IO layer silently decodes in pure Python
    if subprocess.run(["make", "-C", native_dir], stdout=sys.stderr).returncode:
        print("benchmark: building libgskycodec.so failed", file=sys.stderr)
        return 1
    from gsky_tpu import native
    if native._lib is None:
        print("benchmark: libgskycodec.so did not load", file=sys.stderr)
        return 1

    # the archive is drawn and written (numpy, zlib, file writes: all
    # outside the GIL) while this thread takes the chip
    pool = ThreadPoolExecutor(1)
    archive = pool.submit(build_archive, cell, args.seed, cache)

    from gsky_tpu.device import PlatformError, ensure_platform
    try:
        plat = ensure_platform()
    except PlatformError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if plat["platform"] != ("cpu" if args.rehearsal else "tpu") \
            or len(devices) < cell.chips:
        print(f"benchmark: platform {plat['platform']!r} with "
              f"{len(devices)} device(s); the cell asks for {cell.chips} "
              "TPU chip(s)", file=sys.stderr)
        return 2
    log(f"platform {plat}")
    probe = CompileProbe()
    import logging
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)

    root, crawl = archive.result()
    pool.shutdown()
    conf = write_config(cell.config, root, os.path.join(scratch, "conf"))
    archive_mod = spec.load_kind("archives", cell.config["archive"]["kind"])
    gen = spec.load_kind("generators", cell.traffic["generator"]).Generator(
        cell.traffic, cell.config, archive_mod, args.seed)

    with Server(conf, crawl, os.path.join(scratch, "log"),
                os.path.join(scratch, "tmp")) as server:
        log(f"gsky-ows serving on {server.host}")
        client = Client(server.host)
        plan = gen.window()
        passes, warmed = warm_up(client, gen, server, probe,
                                 cell.traffic["warmup"], plan, seconds)
        log(f"warm-up: {passes}")
        tracer = TraceSlice(args.out, seconds) if args.trace else None
        d0 = server.debug()
        c0 = probe.snapshot()
        setup_s = time.perf_counter() - T_START
        log(f"set-up took {setup_s:.1f} s; measuring for {seconds} s")
        if tracer:
            tracer.start()
        t0 = time.perf_counter()
        results = client.closed(plan.reqs, plan.connections, seconds)
        c1 = probe.snapshot()
        d1 = server.debug()
        stats = devices[0].memory_stats() or {}

        ctx = Ctx(cell=cell, results=results, t0=t0, window_s=seconds,
                  setup_s=setup_s, warmup=passes, warmed=warmed,
                  debug0=d0, debug1=d1,
                  compiles_in_window=(c1[0] - c0[0], c1[1] - c0[1]),
                  device_kind=devices[0].device_kind,
                  hbm_peak_bytes=stats.get("peak_bytes_in_use"))
        breakdown = reduce_trace(tracer, ctx, args.out, spans_file) \
            if tracer else None

        # the checks, outside the window
        t_check = time.perf_counter()
        problems, records = gen.verify(results, client.fetch)
        if not records:
            problems.append("no answer could be checked")
        st = program_state(ctx, cell.traffic)
        problems += program_state_problems(ctx, st)
        checks = checks_of(records, ctx, cell.traffic.get("check", {}), st)
        log(f"checks took {time.perf_counter() - t_check:.1f} s: "
            f"{len(records)} answers, {len(problems)} problem(s)")
        for p in problems[:10]:
            log("PROBLEM: " + p)

    if tracer:
        metrics = read_metrics(cell.per_layer, "layer_metrics", ctx)
    else:
        metrics = read_metrics(cell.end_to_end, "end_to_end", ctx)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.hbm_peak_bytes or 0}
    if tracer and not args.rehearsal:
        device["busy_s"] = ctx.busy_s or 0.0
        device["window_s"] = ctx.traced_s
    failed = [r for r in results if not r.ok]
    lat = np.array([r.latency_s * 1e3 for r in results if r.ok])
    line = {"correct": not problems, "attempted": len(results),
            "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    # why, where it is not correct: the ledger keeps this line's numbers
    line["problems"] = [p[:200] for p in problems[:3]]
    line["checks"] = checks

    report = dict(line, workload=cell.name, seed=args.seed, seconds=seconds,
                  trace=args.trace, rehearsal=args.rehearsal,
                  setup_s=setup_s, warmup=passes, problems=problems,
                  records=records, legs=ctx.legs(),
                  stages_ms_per_tile={
                      k: ctx.ratio([f"tile_stages.{k}"],
                                   ["tile_stages.tiles"], 1e3)
                      for k in ("plan_s", "index_s", "decode_s",
                                "dispatch_s", "readback_s", "encode_s")},
                  latency_ms={q: float(np.percentile(lat, q))
                              for q in (10, 50, 90, 95, 99)}
                  if len(lat) else {},
                  compiles_in_window=sum(ctx.compiles_in_window),
                  first_used_in_window=ctx.first_used(),
                  warmed=warmed, window_halves=ctx.halves(),
                  failures=[{"status": r.status, "key": list(r.req.key),
                             "body": (r.body or b"")[-200:].decode(
                                 "latin-1")} for r in failed[:5]],
                  kernels={w: d1.get("kernels", {}).get(w) for w in
                           ("failed", "demoted", "promoted", "lowered")},
                  total_s=time.perf_counter() - T_START)
    with open(os.path.join(args.out, f"{cell.name}.json"), "w") as fp:
        json.dump(report, fp, indent=1, default=str)
    log(f"legs {report['legs']}")
    log(f"whole run {report['total_s']:.1f} s; report in "
        f"{os.path.join(args.out, cell.name + '.json')}")
    print(json.dumps(line), flush=True)
    for p in line["problems"]:
        print("benchmark: PROBLEM: " + p, file=sys.stderr)
    print("benchmark: checks " + json.dumps(checks), file=sys.stderr,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
