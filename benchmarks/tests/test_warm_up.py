"""run.py's warm-up and the guards that rest on it, on a made-up server:
how long the twinned head gets is decided by what the passes show."""

import itertools
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run      # noqa: E402
from benchmarks.plan import Plan       # noqa: E402


class Fake:
    """Client, generator, server and compile probe in one: pass k of
    twins takes `pass_s[k]` seconds on the clock `run.time` reads."""

    def __init__(self, pass_s, monkeypatch, fill=()):
        self.pass_s, self.now, self.sent = list(pass_s), 0.0, []
        self.fill, self.drill_host = list(fill), 0
        monkeypatch.setattr(run.time, "perf_counter", lambda: self.now)

    def closed(self, reqs, connections):
        self.sent.append(list(reqs))
        self.now += self.pass_s.pop(0) if self.pass_s else 1.0
        return [SimpleNamespace(ok=True) for _ in self.sent[-1]]

    def prefill(self):
        return self.fill

    def twins(self, reqs):
        return [("twin", r) for r in reqs]

    def snapshot(self):
        return (0, len(self.sent))

    def debug(self):
        return {"executor": {"dispatches": {"drill_host": self.drill_host}}}


def warm(fake, w, seconds=20.0, supply=None):
    plan = Plan(6, iter(range(10 ** 6) if supply is None else supply))
    passes, warmed = run.warm_up(fake, fake, fake, fake, w, plan, seconds)
    return plan, passes, warmed


TWINS = {"head_margin": 1.5, "head_per_s": 10, "pass_requests": 100,
         "twin_seconds_max": 1000}


def test_the_head_follows_the_fastest_pass(monkeypatch):
    # 100 requests in 5 s, then 2 s, then 1 s: 20, 50, 100 requests/s;
    # at 100/s a 20 s window takes 2,000, and the margin makes it 3,000
    fake = Fake([5.0, 2.0] + [1.0] * 40, monkeypatch)
    plan, passes, warmed = warm(fake, TWINS)
    assert warmed == 3000 and len(passes) == 30
    assert all(p["phase"] == "twins" and p["loads"] == 1 for p in passes)
    # each pass twins the next requests of the window's own sequence ...
    assert fake.sent[1] == [("twin", r) for r in range(100, 200)]
    # ... and the window still begins with the first of them
    assert list(itertools.islice(plan.reqs, 3001))[-2:] == [2999, 3000]


def test_a_faster_server_gets_a_longer_head(monkeypatch):
    slow = warm(Fake([1.0] * 99, monkeypatch), TWINS)[2]
    fast = warm(Fake([0.5] * 99, monkeypatch), TWINS)[2]
    assert (slow, fast) == (3000, 6000)


def test_the_floor_holds_when_every_pass_is_slow(monkeypatch):
    # a cold compile cache: 2 requests/s in every pass
    w = dict(TWINS, head_per_s=40)
    assert warm(Fake([50.0] * 99, monkeypatch), w)[2] == 800


def test_the_time_limit_and_the_end_of_the_sequence_stop_it(monkeypatch):
    w = dict(TWINS, twin_seconds_max=3.5)
    assert warm(Fake([1.0] * 99, monkeypatch), w)[2] == 400
    assert warm(Fake([1.0] * 99, monkeypatch), TWINS,
                supply=range(250))[2] == 250


def test_prefill_is_sent_until_the_counters_stand_still(monkeypatch):
    fake = Fake([], monkeypatch, fill=list(range(16)))
    sends = []

    def closed(reqs, connections):
        sends.append(list(reqs))
        fake.now += 1.0
        fake.drill_host += 3 if len(sends) < 3 else 0   # still by pass 3
        return [SimpleNamespace(ok=True) for _ in reqs]
    fake.closed = closed
    w = {"pass_requests": 16, "settle_seconds": 120,
         "until_still": ["executor.dispatches.drill_host"]}
    _, passes, warmed = warm(fake, w)
    assert warmed is None           # no twins asked for
    assert [p["moving"] for p in passes] == \
        [["executor.dispatches.drill_host"]] * 2 + [[]]
    assert sends == [list(range(16))] * 3


def ctx(**kw):
    base = dict(compiles_in_window=(0, 0), warmed=None, results=[0] * 500,
                debug1={}, delta=lambda path: 0, first_used=lambda: ["leg"])
    return SimpleNamespace(**dict(base, **kw))


def problems_of(c, traffic):
    return run.program_state_problems(c, run.program_state(c, traffic))


def checks_of(records, c, traffic):
    return run.checks_of(records, c, traffic.get("check", {}),
                         run.program_state(c, traffic))


def test_a_window_that_outran_its_warm_up_is_not_correct():
    assert problems_of(ctx(), {}) == []
    assert problems_of(ctx(warmed=500), {}) == []
    late, = problems_of(ctx(warmed=499), {})
    assert "500 requests" in late and "499" in late


def test_a_stray_load_is_borne_and_a_compile_or_a_run_of_loads_is_not():
    stray = (0, run.STRAY_LOADS)
    assert problems_of(ctx(compiles_in_window=stray), {}) == []
    many, = problems_of(
        ctx(compiles_in_window=(0, run.STRAY_LOADS + 1)), {})
    assert "3 loaded" in many and "leg" in many
    fresh, = problems_of(ctx(compiles_in_window=(1, 0)), {})
    assert "1 program(s) compiled" in fresh


COMMON = ["answers_checked", "served_twice_differs", "rows_malformed",
          "rows_empty_on_nodata", "rows_undecided", "compiled_in_window",
          "loaded_in_window", "loaded_in_window_bound", "sent",
          "demand_moved", "kernel_incidents", "guard_incidents"]


def test_checks_holds_every_number_compared_beside_its_limit():
    """The line's `checks`, by the kind of cell: finite numbers under
    fixed names, so that the ledger's `last_line_numbers` can say why a
    run was not correct."""
    import math
    tiles = checks_of(
        [{"mismatch": 0.0004, "served_twice": False},
         {"mismatch": 0.0031, "served_twice": True}, {}],
        ctx(compiles_in_window=(1, 3), warmed=499),
        {"check": {"tiles": 8, "bound_mismatch": 0.002}})
    assert sorted(tiles) == sorted(
        COMMON + ["mismatch_max", "mismatch_bound", "warmed"])
    assert (tiles["answers_checked"], tiles["mismatch_max"],
            tiles["mismatch_bound"], tiles["served_twice_differs"]) \
        == (2, 0.0031, 0.002, 1)
    assert (tiles["compiled_in_window"], tiles["loaded_in_window"],
            tiles["loaded_in_window_bound"], tiles["sent"],
            tiles["warmed"]) == (1, 3, run.STRAY_LOADS, 500, 499)

    moved = {"executor.dispatches.drill_host": 2}
    debug1 = {"kernels": {"failed": ["k"], "lowered": {"m": ["interpret"]}},
              "device": {"hangs": 1, "ooms": 2, "crashes": 0}}
    drills = checks_of(
        [{"rows": "finite"}, {"rows": "empty_on_nodata"},
         {"rows": "undecided"}, {"rows": "malformed"},
         {"rect": [0, 1, 0, 1], "rows": "finite", "max_abs_err": 5e-5}],
        ctx(debug1=debug1, delta=lambda path: moved.get(path, 0)),
        {"check": {"rect_px": [16], "bound_abs": 2e-4},
         "demand_still": list(moved) + ["executor.dispatches.other"]})
    assert sorted(drills) == sorted(COMMON + ["abs_err_max", "abs_err_bound"])
    assert (drills["answers_checked"], drills["abs_err_max"],
            drills["abs_err_bound"]) == (5, 5e-5, 2e-4)
    assert (drills["rows_malformed"], drills["rows_empty_on_nodata"],
            drills["rows_undecided"]) == (1, 1, 1)
    assert (drills["demand_moved"], drills["kernel_incidents"],
            drills["guard_incidents"]) == (2, 2, 3)
    for checks in (tiles, drills):
        assert all(isinstance(v, (int, float)) and not isinstance(v, bool)
                   and math.isfinite(v) for v in checks.values()), checks
    # the problems say the same as the numbers
    assert len(problems_of(
        ctx(debug1=debug1, delta=lambda path: moved.get(path, 0)),
        {"demand_still": list(moved)})) == 4
