"""Mesh wave dispatch: one device program spanning every chip.

The wave scheduler (pipeline/waves.py) already coalesces a tick's
requests into one paged program per (kind, statics, pool) group; this
module is the branch ABOVE that dispatch.  When ``GSKY_MESH=1`` the
scheduler hands each drained group here, the group's descriptor walks
the partition-rule table (mesh/rules.py), and the selected layout
decides how the stacked program spreads over the mesh:

- ``granule`` — the wave's stacked tables / params / ctrls get a
  `NamedSharding` over the flattened mesh (wave axis split across all
  chips, page pool replicated) feeding ONE `shard_map` program whose
  local body is the unchanged paged kernel.  Paged rows are
  bit-independent (ns_id -1 padding, test_waves parity), so the mesh
  tile bytes equal the single-chip wave bytes exactly.  Animation
  frame lanes (GSKY_ANIM, docs/PERF.md "Temporal waves") ride this
  layout too: each lane carries its timestep's granule ``serials`` and
  the sharded planner (autoplan.plan_sharded) merges same-serial lanes
  into shared-halo superblocks per chip — the `temporal_lanes` stat
  below counts how many mesh lanes were temporal.
- ``x`` — each entry re-renders through the mesh-owned `SpmdRenderer`
  (granule x width `shard_map`): intra-tile parallelism for the 4K+
  WCS export blocks that would serialise a whole chip.
- ``time`` — the stacked (K, B, N) drill reduction is `device_put`
  with a `NamedSharding` over K and jit auto-partitions
  `wave_drill_stats` across every chip (row-independent reduction:
  bit-identical to the single-chip wave).
- ``replicated`` — the scheduler's own single-chip dispatch, untouched.

Failure semantics are the scheduler's: every layout runs inside
`device_guard.run("dispatch.wave")`, and an incident fails the wave's
entries over INDIVIDUALLY to their per-call legs — never as a wave.
Mesh results skip the single-device output ring (their shards live on
their chips until the drainer gathers them); the drainer's shard
observer records per-chip readiness skew before the gather.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.metrics import (MESH_CHIP_OCCUPANCY, MESH_SHARD_SKEW_MS,
                           MESH_WAVES)
from ..parallel.mesh import AXIS_GRANULE, AXIS_X, make_mesh
from . import rules as rules_mod

# the wave/time axis shards over BOTH mesh axes flattened — every chip
# takes rows regardless of the (granule, x) factorisation
MESH_AXES = (AXIS_GRANULE, AXIS_X)


def mesh_enabled() -> bool:
    """GSKY_MESH=1 and more than one visible device: wave groups route
    through the partition rules.  Unset or 0 keeps single-chip waves
    byte-identically (the mesh branch is never consulted)."""
    if os.environ.get("GSKY_MESH", "0") != "1":
        return False
    try:
        return len(jax.devices()) > 1
    except Exception:  # pragma: no cover
        return False


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class MeshDispatcher:
    """Rule-driven mesh dispatch for wave groups + the process-wide
    owner of the sharded production programs (`SpmdRenderer`)."""

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_chips = int(self.mesh.devices.size)
        # exactly one sharded code path: the old GSKY_SPMD entry
        # points (executor/drill compat shim) and the mesh `x` layout
        # share this renderer and its program cache
        from ..parallel.spmd import SpmdRenderer
        self.spmd = SpmdRenderer(self.mesh)
        # parse once at construction: a malformed GSKY_MESH_RULES is a
        # loud startup error, not a silent per-wave fallback
        self.rules = rules_mod.active_rules()
        self._fns = {}
        self._lock = threading.Lock()
        # counters (under _lock)
        self.waves_by_layout: Dict[str, int] = {}
        self.entries_by_layout: Dict[str, int] = {}
        # animation frame lanes (payload carries granule serials):
        # how much of the mesh traffic is temporal, per layout
        self.temporal_by_layout: Dict[str, int] = {}
        self.skew_ms_last = 0.0
        from ..obs import tsan
        if tsan.enabled():
            # lockset tracking across ticker/drainer/scrape threads
            # (docs/ANALYSIS.md "Race sanitizer")
            tsan.track(self, "MeshDispatcher")

    # -- rules ---------------------------------------------------------

    def layout_for(self, kind: str, key: tuple, wave: int) -> str:
        try:
            desc = rules_mod.describe(kind, key, wave)
        except Exception:
            return "replicated"
        return rules_mod.match_rules(desc, self.rules)

    # -- shardings / program cache -------------------------------------

    def _wave_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(MESH_AXES))

    def _rep_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def _wave_pad(self, n: int) -> int:
        """Pad the wave axis pow2 (kernel-shape reuse, same as the
        single-chip wave) then up to a chip-count multiple so the
        `NamedSharding` splits evenly."""
        p = _pow2(n)
        return -(-p // self.n_chips) * self.n_chips

    def _get(self, key, builder):
        with self._lock:
            fn = self._fns.get(key)
            if fn is None:
                fn = builder()
                self._fns[key] = fn
            return fn

    def _stack_tables(self, es, Np: int):
        """The scheduler's ragged stacking, kept (Np, T, W) so the
        params rows shard with their wave rows (the scheduler reshapes
        to (Np*T, W) pre-dispatch; here the local body does)."""
        from ..ops.paged import PARAMS_W
        T = max(e.payload["tables"].shape[0] for e in es)
        S = max(e.payload["tables"].shape[1] for e in es)
        tables = np.zeros((Np, T, S), np.int32)
        params = np.zeros((Np, T, PARAMS_W), np.float32)
        params[:, :, 10] = -1.0     # ns_id: padding rows gather nothing
        for i, e in enumerate(es):
            ti, si = e.payload["tables"].shape
            tables[i, :ti, :si] = e.payload["tables"]
            params[i, :ti] = e.payload["params16"]
        return tables, params, T, S

    def _build_wave_byte(self, method, n_ns, out_hw, step, auto,
                         colour_scale, T, interpret):
        from ..ops.paged import PARAMS_W, render_byte_paged

        def local(parr, tables, params, ctrls, sps):
            n_l = tables.shape[0]
            return render_byte_paged(
                parr, tables, params.reshape(n_l * T, PARAMS_W), ctrls,
                sps, method, n_ns, out_hw, step, auto, colour_scale,
                interpret=interpret)

        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(MESH_AXES), P(MESH_AXES), P(MESH_AXES),
                      P(MESH_AXES)),
            out_specs=P(MESH_AXES), check_vma=False)
        return jax.jit(fn)

    def _build_wave_expr(self, method, n_ns, out_hw, step, auto,
                         colour_scale, fpk, T, interpret):
        """Granule-sharded fused band algebra: the local body is the
        unchanged paged gather + expression epilogue + scale-to-byte
        (`render_expr_paged`), so mesh tile bytes equal the
        single-chip wave bytes exactly (same row-independence argument
        as the byte layout)."""
        from ..ops.paged import PARAMS_W, render_expr_paged

        def local(parr, tables, params, ctrls, sps, consts):
            n_l = tables.shape[0]
            return render_expr_paged(
                parr, tables, params.reshape(n_l * T, PARAMS_W), ctrls,
                sps, consts, method, n_ns, out_hw, step, auto,
                colour_scale, fpk, interpret=interpret)

        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(MESH_AXES), P(MESH_AXES), P(MESH_AXES),
                      P(MESH_AXES), P(MESH_AXES)),
            out_specs=P(MESH_AXES), check_vma=False)
        return jax.jit(fn)

    def _build_wave_expr_sb(self, method, n_ns, out_hw, step, auto,
                            colour_scale, fpk, T, blk, interpret):
        from ..ops.paged import PARAMS_W, render_expr_paged

        def local(parr, tables, params, ctrls, sps, consts, sb_of):
            n_l = params.shape[0]
            return render_expr_paged(
                parr, tables, params.reshape(n_l * T, PARAMS_W), ctrls,
                sps, consts, method, n_ns, out_hw, step, auto,
                colour_scale, fpk, interpret=interpret, blk=blk,
                sb_of=sb_of)

        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(MESH_AXES), P(MESH_AXES), P(MESH_AXES),
                      P(MESH_AXES), P(MESH_AXES), P(MESH_AXES)),
            out_specs=P(MESH_AXES), check_vma=False)
        return jax.jit(fn)

    def _build_wave_scored(self, method, n_ns, out_hw, step, T,
                           interpret):
        from ..ops.paged import PARAMS_W, warp_scored_paged

        def local(parr, tables, params, ctrls):
            n_l = tables.shape[0]
            canv, best = warp_scored_paged(
                parr, tables, params.reshape(n_l * T, PARAMS_W), ctrls,
                method, n_ns, out_hw, step, interpret=interpret)
            # fold best -> validity before anything leaves the chip:
            # the -inf invalid marker must not reach guarded_readback
            # (same invariant as the single-chip wave)
            return canv, best > -jnp.inf

        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(MESH_AXES), P(MESH_AXES), P(MESH_AXES)),
            out_specs=(P(MESH_AXES), P(MESH_AXES)), check_vma=False)
        return jax.jit(fn)

    def _build_wave_byte_sb(self, method, n_ns, out_hw, step, auto,
                            colour_scale, T, blk, interpret):
        """Superblock variant: the chip-local body gathers its Gc
        union regions once and broadcasts them to its rpc lanes via
        the chip-LOCAL ``sb_of`` map — the autoplanner sliced the wave
        per chip, so no superblock (and no halo) crosses the shard
        boundary."""
        from ..ops.paged import PARAMS_W, render_byte_paged

        def local(parr, tables, params, ctrls, sps, sb_of):
            n_l = params.shape[0]
            return render_byte_paged(
                parr, tables, params.reshape(n_l * T, PARAMS_W), ctrls,
                sps, method, n_ns, out_hw, step, auto, colour_scale,
                interpret=interpret, blk=blk, sb_of=sb_of)

        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(MESH_AXES), P(MESH_AXES), P(MESH_AXES),
                      P(MESH_AXES), P(MESH_AXES)),
            out_specs=P(MESH_AXES), check_vma=False)
        return jax.jit(fn)

    def _build_wave_scored_sb(self, method, n_ns, out_hw, step, T,
                              blk, interpret):
        from ..ops.paged import PARAMS_W, warp_scored_paged

        def local(parr, tables, params, ctrls, sb_of):
            n_l = params.shape[0]
            canv, best = warp_scored_paged(
                parr, tables, params.reshape(n_l * T, PARAMS_W), ctrls,
                method, n_ns, out_hw, step, interpret=interpret,
                blk=blk, sb_of=sb_of)
            return canv, best > -jnp.inf

        fn = shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(MESH_AXES), P(MESH_AXES), P(MESH_AXES),
                      P(MESH_AXES)),
            out_specs=(P(MESH_AXES), P(MESH_AXES)), check_vma=False)
        return jax.jit(fn)

    # -- per-layout dispatch -------------------------------------------

    def dispatch_wave(self, sched, kind: str, es: List, staged=None):
        """The scheduler's mesh entry: pick the layout, dispatch, and
        account.  Runs inside device_guard.run('dispatch.wave'); raises
        propagate to the scheduler's per-entry failover.  ``staged``
        is the `stage_wave` handoff (per-chip slices already uploaded
        while the previous sharded program ran); only the granule
        layout stages, other layouts ignore it."""
        layout = self.layout_for(kind, es[0].key, len(es))
        if layout == "granule" and kind in ("byte", "scored", "expr"):
            devs = self._dispatch_wave_granule(kind, es, staged)
        elif layout == "x" and kind in ("byte", "scored"):
            devs = self._dispatch_x(kind, es)
        elif layout == "time" and kind == "drill":
            devs = self._dispatch_drill_time(es)
        else:
            # replicated fallback — or an operator rule pairing a kind
            # with a layout it cannot take (a drill has no x axis):
            # the group dispatches single-chip, byte-identical
            layout = "replicated"
            devs = sched._dispatch_group(kind, es)
        self._note(layout, es)
        return devs

    def stage_wave(self, sched, kind: str, es: List):
        """The ASSEMBLY-stage half of the granule layout: plan the
        shard split, stack the wave's tables/params/ctrls and issue
        the `NamedSharding` `device_put` uploads NOW — the per-chip
        slices transfer while the previous sharded program is still
        executing.  Returns the staged handoff dict for
        `dispatch_wave(..., staged=...)`, or None when the group's
        layout doesn't pre-stage (x / time / replicated re-stack at
        dispatch, unchanged).  Runs under
        device_guard.run('mesh.stage') — a staging-class site, so a
        hang queued behind a wedged kernel is attributed to the
        EXECUTING wave."""
        layout = self.layout_for(kind, es[0].key, len(es))
        if layout != "granule" or kind not in ("byte", "scored",
                                               "expr"):
            return None
        return self._stage_granule(kind, es)

    def _stage_granule(self, kind: str, es: List) -> Dict:
        """Shared plan/stack/upload: the assembly stage calls it one
        wave ahead (via `stage_wave`); the synchronous leg calls it
        inline at dispatch — identical buffers either way."""
        from ..ops import paged
        N = len(es)
        Np = self._wave_pad(N)
        plan = None
        try:
            from ..pipeline import autoplan
            plan = autoplan.plan_sharded(kind, es, self.n_chips, Np)
        except Exception:   # planning is an optimisation
            plan = None
        if plan is not None:
            tables, params = plan.tables, plan.params
            T, S = int(params.shape[1]), int(tables.shape[2])
            blk, sb_of = plan.blk, plan.sb_of
            paged.note_gather(plan.planned_bytes)
        else:
            pool = es[0].payload["pool"]
            tables, params, T, S = self._stack_tables(es, Np)
            blk, sb_of = None, None
            paged.note_gather(paged.table_gather_bytes(
                tables, pool.page_rows, pool.page_cols))
        ctrls = np.stack([e.payload["ctrl"] for e in es]
                         + [es[0].payload["ctrl"]] * (Np - N))
        wav = self._wave_sharding()
        staged = {
            "layout": "granule", "Np": Np, "T": T, "S": S, "blk": blk,
            "d_tables": jax.device_put(jnp.asarray(tables), wav),
            "d_params": jax.device_put(jnp.asarray(params), wav),
            "d_ctrls": jax.device_put(jnp.asarray(ctrls), wav),
            "d_sb": None if sb_of is None else
            jax.device_put(jnp.asarray(sb_of), wav),
        }
        if kind in ("byte", "expr"):
            sps = np.stack([e.payload["sp"] for e in es]
                           + [es[0].payload["sp"]] * (Np - N))
            staged["d_sps"] = jax.device_put(jnp.asarray(sps), wav)
        if kind == "expr":
            consts = np.stack([e.payload["consts"] for e in es]
                              + [es[0].payload["consts"]] * (Np - N))
            staged["d_consts"] = jax.device_put(jnp.asarray(consts),
                                                wav)
        return staged

    def _chip_counts(self, n_real: int, n_padded: int) -> List[int]:
        """Real entries landing on each chip under the wave-axis
        split (chip i owns rows [i*rpc, (i+1)*rpc))."""
        rpc = max(1, n_padded // self.n_chips)
        return [max(0, min(n_real - c * rpc, rpc))
                for c in range(self.n_chips)]

    def _dispatch_wave_granule(self, kind: str, es: List, staged=None):
        pool = es[0].payload["pool"]
        statics = es[0].key[0]
        try:
            from ..ops.pallas_tpu import pallas_interpret
            interpret = pallas_interpret()
            N = len(es)
            if staged is None:
                staged = self._stage_granule(kind, es)
            Np = staged["Np"]
            T, S, blk = staged["T"], staged["S"], staged["blk"]
            d_tables = staged["d_tables"]
            d_params = staged["d_params"]
            d_ctrls = staged["d_ctrls"]
            d_sb = staged["d_sb"]
            rep = self._rep_sharding()
            self._chip_occupancy(self._chip_counts(N, Np))
            if kind == "byte":
                method, n_ns, out_hw, step, auto, colour_scale = statics
                d_sps = staged["d_sps"]
                if d_sb is not None:
                    Gc = int(d_tables.shape[0]) // self.n_chips
                    fn = self._get(
                        ("wave_byte_sb", statics, T, S, Np, Gc, blk,
                         interpret),
                        lambda: self._build_wave_byte_sb(
                            method, n_ns, out_hw, step, auto,
                            colour_scale, T, blk, interpret))
                    with pool.locked_pool() as parr:
                        out = fn(jax.device_put(parr, rep), d_tables,
                                 d_params, d_ctrls, d_sps, d_sb)
                    return (out[:N],)
                fn = self._get(
                    ("wave_byte", statics, T, S, Np, interpret),
                    lambda: self._build_wave_byte(
                        method, n_ns, out_hw, step, auto, colour_scale,
                        T, interpret))
                with pool.locked_pool() as parr:
                    out = fn(jax.device_put(parr, rep), d_tables,
                             d_params, d_ctrls, d_sps)
                return (out[:N],)
            if kind == "expr":
                from ..ops.paged import note_expr_fused, \
                    note_expr_program
                from ..ops.expr import fingerprint_hash
                (method, n_ns, out_hw, step, auto, colour_scale,
                 fpk) = statics
                note_expr_fused("mesh")
                note_expr_program(fingerprint_hash(fpk))
                d_sps = staged["d_sps"]
                d_consts = staged["d_consts"]
                if d_sb is not None:
                    Gc = int(d_tables.shape[0]) // self.n_chips
                    fn = self._get(
                        ("wave_expr_sb", statics, T, S, Np, Gc, blk,
                         interpret),
                        lambda: self._build_wave_expr_sb(
                            method, n_ns, out_hw, step, auto,
                            colour_scale, fpk, T, blk, interpret))
                    with pool.locked_pool() as parr:
                        out = fn(jax.device_put(parr, rep), d_tables,
                                 d_params, d_ctrls, d_sps, d_consts,
                                 d_sb)
                    return (out[:N],)
                fn = self._get(
                    ("wave_expr", statics, T, S, Np, interpret),
                    lambda: self._build_wave_expr(
                        method, n_ns, out_hw, step, auto, colour_scale,
                        fpk, T, interpret))
                with pool.locked_pool() as parr:
                    out = fn(jax.device_put(parr, rep), d_tables,
                             d_params, d_ctrls, d_sps, d_consts)
                return (out[:N],)
            method, n_ns, out_hw, step = statics
            if d_sb is not None:
                Gc = int(d_tables.shape[0]) // self.n_chips
                fn = self._get(
                    ("wave_scored_sb", statics, T, S, Np, Gc, blk,
                     interpret),
                    lambda: self._build_wave_scored_sb(
                        method, n_ns, out_hw, step, T, blk, interpret))
                with pool.locked_pool() as parr:
                    canv, valid = fn(jax.device_put(parr, rep),
                                     d_tables, d_params, d_ctrls, d_sb)
                return (canv[:N], valid[:N])
            fn = self._get(
                ("wave_scored", statics, T, S, Np, interpret),
                lambda: self._build_wave_scored(
                    method, n_ns, out_hw, step, T, interpret))
            with pool.locked_pool() as parr:
                canv, valid = fn(jax.device_put(parr, rep), d_tables,
                                 d_params, d_ctrls)
            return (canv[:N], valid[:N])
        finally:
            for e in es:
                e.cleanup_once()

    def _dispatch_x(self, kind: str, es: List):
        """4K+ export blocks: one sharded program per ENTRY (granule x
        width strips through the mesh-owned SpmdRenderer), every chip
        on every block — intra-tile parallelism, where a wide block
        would otherwise serialise one chip.  The entries' bucketed
        payloads (stack, params, win) feed the renderer directly; the
        page tables are unpinned in the finally (this layout reads the
        scene stacks, not the pool)."""
        statics = es[0].key[0]
        try:
            self._chip_occupancy([len(es)] * self.n_chips)
            if kind == "byte":
                method, n_ns, out_hw, step, auto, colour_scale = statics
                outs = []
                for e in es:
                    stack, bparams, bwin, bwin0 = e.payload["xla"]
                    outs.append(self.spmd.render_composite(
                        stack, jnp.asarray(e.payload["ctrl"]), bparams,
                        jnp.asarray(e.payload["sp"]), method, n_ns,
                        out_hw, step, auto, colour_scale, win=bwin,
                        win0=bwin0))
                return (jnp.stack(outs),)
            method, n_ns, out_hw, step = statics
            cs, vs = [], []
            for e in es:
                stack, bparams, bwin, bwin0 = e.payload["xla"]
                canv, best = self.spmd.mosaic_scored(
                    stack, jnp.asarray(e.payload["ctrl"]), bparams,
                    method, n_ns, out_hw, step, win=bwin, win0=bwin0)
                cs.append(canv)
                vs.append(best > -jnp.inf)
            return (jnp.stack(cs), jnp.stack(vs))
        finally:
            for e in es:
                e.cleanup_once()

    def _dispatch_drill_time(self, es: List):
        from ..ops.paged import wave_drill_stats
        clip_lo, clip_hi, pix = es[0].key[1:]
        K = len(es)
        Kp = self._wave_pad(K)
        data = jnp.stack([jnp.asarray(e.payload["data"]) for e in es]
                         + [jnp.asarray(es[0].payload["data"])]
                         * (Kp - K))
        valid = jnp.stack([jnp.asarray(e.payload["valid"])
                           for e in es]
                          + [jnp.asarray(es[0].payload["valid"])]
                          * (Kp - K))
        wav = self._wave_sharding()
        vals, counts = wave_drill_stats(
            jax.device_put(data, wav), jax.device_put(valid, wav),
            clip_lo, clip_hi, pixel_count=pix)
        self._chip_occupancy(self._chip_counts(K, Kp))
        return (vals[:K], counts[:K])

    # -- prewarm -------------------------------------------------------

    def prewarm_programs(self, pool, specs, sizes, batches, slots,
                         wave_sizes, step: int = 16) -> int:
        """Compile the mesh wave programs off the request path —
        server/prewarm.py extends its paged lattice with the
        mesh-layout axis by handing the same (method, granule-pow2,
        slot-pow2, wave-size-pow2) sweep here.  For every point this
        compiles the granule-sharded byte + scored programs (null
        tables: the gather walks real NaN pages on every chip), and
        per wave size the time-sharded drill reduction.  Returns the
        number of programs exercised; failures raise (the caller's
        `run` guard books them)."""
        from ..ops.paged import PARAMS_W
        from ..ops.pallas_tpu import pallas_interpret
        interpret = pallas_interpret()
        wav = self._wave_sharding()
        rep = self._rep_sharding()
        n = 0
        for method, n_exprs, auto, colour_scale in sorted(specs):
            if n_exprs != 1:
                continue        # the paged path is single-band
            for hw in sizes:
                for T in batches:
                    for S in slots:
                        for W in wave_sizes:
                            Np = self._wave_pad(W)
                            tables = jax.device_put(
                                jnp.zeros((Np, T, S), jnp.int32), wav)
                            params = np.zeros((Np, T, PARAMS_W),
                                              np.float32)
                            params[:, :, 10] = -1.0
                            params[:, :, 13] = pool.page_rows
                            params[:, :, 14] = pool.page_cols
                            params[:, :, 15] = 1.0
                            d_params = jax.device_put(
                                jnp.asarray(params), wav)
                            gh = (hw - 1 + step - 1) // step + 1
                            ctrls = jax.device_put(
                                jnp.zeros((Np, 2, gh, gh), jnp.float32),
                                wav)
                            sps = jax.device_put(
                                jnp.zeros((Np, 3), jnp.float32), wav)
                            sb = (method, 1, (hw, hw), step, auto,
                                  colour_scale)
                            fnb = self._get(
                                ("wave_byte", sb, T, S, Np, interpret),
                                lambda: self._build_wave_byte(
                                    method, 1, (hw, hw), step, auto,
                                    colour_scale, T, interpret))
                            ss = (method, 1, (hw, hw), step)
                            fns = self._get(
                                ("wave_scored", ss, T, S, Np,
                                 interpret),
                                lambda: self._build_wave_scored(
                                    method, 1, (hw, hw), step, T,
                                    interpret))
                            with pool.locked_pool() as parr:
                                prep = jax.device_put(parr, rep)
                                jax.block_until_ready(
                                    fnb(prep, tables, d_params, ctrls,
                                        sps))
                                jax.block_until_ready(
                                    fns(prep, tables, d_params, ctrls))
                            n += 2
        from ..ops.paged import wave_drill_stats
        for W in wave_sizes:
            Kp = self._wave_pad(W)
            data = jax.device_put(
                jnp.zeros((Kp, 1, 64), jnp.float32), wav)
            valid = jax.device_put(jnp.ones((Kp, 1, 64), bool), wav)
            for pix in (False, True):
                jax.block_until_ready(wave_drill_stats(
                    data, valid, -3e38, 3e38, pixel_count=pix))
                n += 1
        return n

    # -- accounting ----------------------------------------------------

    def _note(self, layout: str, es: List):
        n_temporal = sum(1 for e in es
                         if e.payload.get("serials") is not None)
        with self._lock:
            self.waves_by_layout[layout] = \
                self.waves_by_layout.get(layout, 0) + 1
            self.entries_by_layout[layout] = \
                self.entries_by_layout.get(layout, 0) + len(es)
            if n_temporal:
                self.temporal_by_layout[layout] = \
                    self.temporal_by_layout.get(layout, 0) + n_temporal
        try:
            MESH_WAVES.labels(layout=layout).inc()
        except Exception:  # prom telemetry only
            pass

    def _chip_occupancy(self, counts: List[int]):
        try:
            for c in counts:
                MESH_CHIP_OCCUPANCY.observe(float(c))
        except Exception:  # prom telemetry only
            pass

    def observe_shards(self, devs):
        """Drainer-side shard probe, called BEFORE the host gather:
        block per chip shard in turn and record the readiness spread —
        the straggler signal for the skew histogram.  The first shard
        absorbs the whole wave wait, so the spread is a lower bound."""
        try:
            shards = list(devs[0].addressable_shards)
            if len(shards) < 2:
                return
            times = []
            for s in shards:
                t0 = time.perf_counter()
                jax.block_until_ready(s.data)
                times.append((time.perf_counter() - t0) * 1e3)
            skew = max(times) - min(times)
            with self._lock:
                self.skew_ms_last = skew
            MESH_SHARD_SKEW_MS.observe(skew)
        except Exception:  # telemetry only — never fail a readback
            pass

    def stats(self) -> Dict:
        with self._lock:
            return {"enabled": mesh_enabled(),
                    "chips": self.n_chips,
                    "mesh": {k: int(v)
                             for k, v in self.mesh.shape.items()},
                    "rules": [(r.source, r.layout) for r in self.rules],
                    "waves_by_layout": dict(self.waves_by_layout),
                    "entries_by_layout": dict(self.entries_by_layout),
                    "temporal_lanes": dict(self.temporal_by_layout),
                    "skew_ms_last": round(self.skew_ms_last, 3),
                    "programs": len(self._fns)
                    + len(self.spmd._fns)}


# -- module singleton ---------------------------------------------------

_default: Optional[MeshDispatcher] = None
_default_lock = threading.Lock()


def _dispatcher() -> MeshDispatcher:
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = MeshDispatcher()
    return _default


def default_mesh() -> Optional[MeshDispatcher]:
    """The process dispatcher when mesh serving is enabled, else None
    (the wave scheduler then keeps its single-chip path, byte-
    identically)."""
    if not mesh_enabled():
        return None
    return _dispatcher()


def active_mesh() -> Optional[MeshDispatcher]:
    """The live dispatcher or None — scrape collectors must not build
    a mesh (and compile nothing) just to report."""
    return _default


def mesh_stats() -> Dict:
    """Scrape-safe stats: {} until the first mesh consult."""
    return {} if _default is None else _default.stats()


def reset_mesh():
    """Drop the singleton (tests / config reload)."""
    global _default
    with _default_lock:
        _default = None


def compat_spmd():
    """The retired ``GSKY_SPMD`` dryrun routing, served by the mesh
    subsystem: `pipeline.executor` / `pipeline.drill` call this where
    they called `parallel.spmd.default_spmd()`, and get the mesh-owned
    `SpmdRenderer` — exactly one sharded code path process-wide."""
    if os.environ.get("GSKY_SPMD", "0") != "1":
        return None
    try:
        if len(jax.devices()) <= 1:
            return None
    except Exception:  # pragma: no cover
        return None
    return _dispatcher().spmd
