"""What a drill's rows are held to (`generators/polygons.py::verify`), at
rehearsal size on made-up answers: every timestep a row, every band a
field, finite where the polygon's footprint holds a valid pixel, empty
where it holds none, either within a pixel of the nodata block's edge;
the seeded rectangles by their values too.  And `run.py::checks_of`
counts each kind."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import reference, run, spec     # noqa: E402
from benchmarks.plan import Result              # noqa: E402

SEED = 2147483659


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell("modis-fc-drill.polygons-warm", rehearsal=True)


@pytest.fixture(scope="module")
def gen(cell):
    archive = spec.load_kind("archives", cell.config["archive"]["kind"])
    return spec.load_kind("generators", cell.traffic["generator"]).Generator(
        cell.traffic, cell.config, archive, SEED)


def body(gen, values):
    """An Execute response whose CSV block holds `values` (T, bands);
    NaN is an empty field."""
    dates = gen.archive.dates(gen.p)
    lines = [d[:10] + "".join("," + ("" if np.isnan(v) else f"{v:.4f}")
                              for v in row)
             for d, row in zip(dates, values)]
    return ("<wps:ExecuteResponse>date,a,b,c\n" + "\n".join(lines)
            + "</wps:ExecuteResponse>").encode()


def drill(gen, cols, rows, values):
    """The window's answer to a triangle with these corners (pixels)."""
    p = gen.p
    cols, rows = np.array(cols, float), np.array(rows, float)
    ring = [(p["origin"][0] + c * p["res"], p["origin"][1] - r * p["res"])
            for c, r in zip(cols, rows)]
    req = gen._req(ring + ring[:1], corners_px=(cols, rows), side_px=3.0)
    return Result(req, 0.0, 0.0, 200, True, 0, 0, b"", body(gen, values))


def rectangles(gen, off=0.0):
    """fetch(): the seeded rectangles answered from the reference, every
    mean `off` too high."""
    fields = gen.archive.fields(gen.p, gen.seed)
    steps = gen.p["steps"]

    def fetch(req):
        r0, r1, c0, c1 = req.meta["rect"]
        mask = np.ones((r1 - r0 + 1, c1 - c0 + 1), bool)
        means, counts = zip(*(reference.drill_means(
            f.window(np.arange(steps), r0, r1 + 1, c0, c1 + 1), mask,
            float(gen.p["nodata"])) for f in fields.values()))
        vals = np.where(np.stack(counts, 1) > 0, np.stack(means, 1) + off,
                        np.nan)
        return Result(req, 0.0, 0.0, 200, True, 0, 0, b"", body(gen, vals))
    return fetch


# the rehearsal's nodata block is rows < 6 and columns < 6 of 96 x 96
IN_BLOCK = ([1.2, 4.0, 2.5], [1.5, 2.0, 4.8])
ON_DATA = ([40.2, 47.0, 43.5], [30.5, 31.0, 36.8])
ON_EDGE = ([2.0, 5.6, 3.0], [2.0, 3.0, 5.4])       # within a pixel of 6


def finite(gen, rows=None):
    return np.full((gen.p["steps"] if rows is None else rows, 3), 0.25)


def empty(gen):
    return np.full((gen.p["steps"], 3), np.nan)


def one_empty_row(gen):
    v = finite(gen)
    v[7] = np.nan
    return v


CASES = [
    # name, corners, values, rectangles' offset, passes, state counted
    ("in_the_block_and_empty", IN_BLOCK, empty, 0.0, True, "empty_on_nodata"),
    ("in_the_block_and_finite", IN_BLOCK, finite, 0.0, False, "malformed"),
    ("on_data_and_finite", ON_DATA, finite, 0.0, True, "finite"),
    ("on_data_with_an_empty_row", ON_DATA, one_empty_row, 0.0, False,
     "malformed"),
    ("on_data_and_empty", ON_DATA, empty, 0.0, False, "malformed"),
    ("a_row_short", ON_DATA, lambda g: finite(g, g.p["steps"] - 1), 0.0,
     False, "malformed"),
    ("on_the_edge_and_finite", ON_EDGE, finite, 0.0, True, "undecided"),
    ("on_the_edge_and_empty", ON_EDGE, empty, 0.0, True, "undecided"),
    ("a_rectangle_off_by_3e-4", ON_DATA, finite, 3e-4, False, "finite"),
    ("a_rectangle_off_by_1e-4", ON_DATA, finite, 1e-4, True, "finite"),
]


@pytest.mark.parametrize("name,corners,values,off,passes,state", CASES,
                         ids=[c[0] for c in CASES])
def test_a_drill_is_held_to_what_its_footprint_holds(
        cell, gen, name, corners, values, off, passes, state):
    results = [drill(gen, *corners, values(gen))]
    problems, records = gen.verify(results, rectangles(gen, off))
    assert bool(problems) != passes, problems
    assert records[0]["rows"] == state
    assert len(records) == 1 + len(cell.traffic["check"]["rect_px"])
    ctx = SimpleNamespace(compiles_in_window=(0, 0), warmed=None,
                          results=results, debug1={}, delta=lambda path: 0)
    checks = run.checks_of(records, ctx, cell.traffic["check"],
                           run.program_state(ctx, cell.traffic))
    counted = {"malformed": "rows_malformed", "undecided": "rows_undecided",
               "empty_on_nodata": "rows_empty_on_nodata"}
    for st, key in counted.items():
        assert checks[key] == (st == state), (key, checks)
    assert checks["answers_checked"] == len(records)
    assert checks["abs_err_bound"] == 2e-4
    assert (checks["abs_err_max"] > checks["abs_err_bound"]) == (off > 2e-4)


def test_a_rectangle_inside_the_block_is_held_to_empty_rows(gen, monkeypatch):
    """A 16-px rectangle can lie in the block too: its rows are empty and
    no value is compared."""
    from benchmarks.generators import polygons
    inside = gen._req([(0, 0)] * 5, rect=(1, 4, 1, 4))
    monkeypatch.setattr(polygons.Generator, "_rectangles",
                        lambda self: [inside])
    problems, records = gen.verify([], rectangles(gen))
    assert not problems and records == [
        {"rect": [1, 4, 1, 4], "holds_data": False,
         "rows": "empty_on_nodata"}]

    def fetch_finite(req):
        return Result(req, 0.0, 0.0, 200, True, 0, 0, b"",
                      body(gen, finite(gen)))
    problems, records = gen.verify([], fetch_finite)
    assert len(problems) == 1 and "holds no data" in problems[0]


def test_the_footprint_rule():
    block = (32, 32)
    hold = reference.footprint_holds_data
    assert hold([1.3, 1.3, 1.3], [2.8, 2.8, 2.8], block) is False   # a point
    assert hold([3, 31.0, 10], [3, 20, 31.0], block) is False
    assert hold([3, 31.2, 10], [3, 20, 30], block) is None
    assert hold([3, 32.9, 10], [3, 20, 30], block) is None
    assert hold([3, 33.0, 10], [3, 20, 30], block) is True
    assert hold([3, 10, 10], [3, 20, 400], block) is True
    assert hold([300, 310, 305], [3, 20, 10], block) is True
