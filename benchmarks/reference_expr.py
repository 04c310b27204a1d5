"""The plain reference for band-expression (band-algebra) tiles.

Float64 numpy on top of `reference.py`, which it imports and does not
copy; nothing from `gsky_tpu.ops`, `gsky_tpu.pipeline` or the executor.
Like `reference.py` it uses `gsky_tpu.geo.crs` point transforms and the
rasters as the archive module made them from the seed, never the files.

A layer's `rgb_products` entry `"ndvi = (nir - red) / (nir + red)"`
names an output and an expression over band namespaces
(`utils/config.go:997-1062` `ParseBandExpressions`).  Per variable: the
granules the layer selects for TIME in that namespace
(`reference.select`), mosaicked newest-wins with the bilinear,
validity-weighted tap (`reference.mosaic`).  The expression is evaluated
per pixel AFTER the per-band mosaic, as upstream's merger does
(`processor/tile_merger.go:523-731`): a pixel holds data where every
variable does and the value is finite (a zero denominator gives no
data), else it is no data.  Then `reference.scale_byte` (offset, clip,
scale, floor to 0..254, 255 = no data; `utils/raster_scaler.go:334`) and
the one-band paletted PNG's colour table (`palette`, below).

The evaluator is this module's own: a recursive-descent parser over
numbers, names, + - * /, unary minus, parentheses, the six comparisons
(1.0 or 0.0) and `c ? a : b` (a where c is not 0), which is what the
configuration's layers and the tests' expressions use.  It never calls
`gsky_tpu/ops/expr.py`.

Departures from upstream, each the program's too: bilinear weights by
validity (as `reference_rgb.py`); the expression is evaluated in
float64 here and in float32 by the program, upstream's govaluate in
float64 over float32 rasters; offset + clip + scale in float32
(`reference.scale_byte` says why); pixel centres are projected one by
one where the program interpolates a 16-px control grid.
"""

import re

import numpy as np

from .reference import mosaic, scale_byte, select

_TOKEN = re.compile(r"\s*(?:(\d+\.?\d*(?:[eE][-+]?\d+)?|\.\d+)"
                    r"|([A-Za-z_][A-Za-z0-9_:.#]*)"
                    r"|(<=|>=|==|!=|[-+*/()<>?:]))")
_COMPARE = {"<": np.less, "<=": np.less_equal, ">": np.greater,
            ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}


def split_product(entry):
    """("ndvi", "(nir - red) / (nir + red)") of an `rgb_products`
    entry `name = expression`."""
    name, _, text = entry.partition("=")
    return name.strip(), text.strip()


def parse(text):
    """The expression as nested tuples: ("num", v), ("var", name),
    ("neg", a), ("bin", op, a, b), ("cmp", op, a, b), ("if", c, a, b).
    Grammar, loosest first: ternary, comparison, + -, * /, unary -."""
    toks, pos = [], 0
    text = text.rstrip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if not m:
            raise ValueError(f"cannot read {text[pos:pos + 10]!r}")
        pos = m.end()
        toks.append(("num", float(m.group(1))) if m.group(1) else
                    ("var", m.group(2)) if m.group(2) else
                    ("op", m.group(3)))
    toks.append(("end", None))
    at = [0]

    def peek():
        return toks[at[0]]

    def take(op=None):
        tok = toks[at[0]]
        if op is not None and tok != ("op", op):
            raise ValueError(f"expected {op!r}, found {tok[1]!r}")
        at[0] += 1
        return tok

    def ternary():
        cond = compare()
        if peek() == ("op", "?"):
            take()
            a = ternary()
            take(":")
            return ("if", cond, a, ternary())
        return cond

    def compare():
        node = add()
        while peek()[0] == "op" and peek()[1] in _COMPARE:
            node = ("cmp", take()[1], node, add())
        return node

    def add():
        node = mul()
        while peek() in (("op", "+"), ("op", "-")):
            node = ("bin", take()[1], node, mul())
        return node

    def mul():
        node = unary()
        while peek() in (("op", "*"), ("op", "/")):
            node = ("bin", take()[1], node, unary())
        return node

    def unary():
        if peek() == ("op", "-"):
            take()
            return ("neg", unary())
        if peek() == ("op", "("):
            take()
            node = ternary()
            take(")")
            return node
        kind, v = take()
        if kind not in ("num", "var"):
            raise ValueError(f"unexpected {v!r}")
        return (kind, v)

    node = ternary()
    if peek()[0] != "end":
        raise ValueError(f"trailing {peek()[1]!r}")
    return node


def variables(node):
    """Names the expression reads, in the order it first reads them."""
    if node[0] == "var":
        return [node[1]]
    out = []
    for child in node[1:]:
        if isinstance(child, tuple):
            out += [v for v in variables(child) if v not in out]
    return out


def evaluate(node, env):
    """The value in float64; `env` maps a name to an array or a number.
    A division by zero gives inf or nan and no warning: `render` turns
    either into no data."""
    kind = node[0]
    if kind == "num":
        return np.float64(node[1])
    if kind == "var":
        return np.asarray(env[node[1]], np.float64)
    if kind == "neg":
        return -evaluate(node[1], env)
    if kind == "if":
        return np.where(evaluate(node[1], env) != 0,
                        evaluate(node[2], env), evaluate(node[3], env))
    a, b = evaluate(node[2], env), evaluate(node[3], env)
    if kind == "cmp":
        return _COMPARE[node[1]](a, b).astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        return {"+": np.add, "-": np.subtract, "*": np.multiply,
                "/": np.divide}[node[1]](a, b)


def select_vars(sources, names, time):
    """{variable: the granules of that namespace whose timestamp is
    TIME} (a `mas` time generator: one date, no accumulation)."""
    return {n: select(sources, n, time) for n in names}


def render_plane(text, per_var, bbox, crs, width, height, method):
    """(values float64, valid): the expression of the per-variable
    mosaics; valid where every variable is and the value is finite."""
    node = parse(text)
    env, valid = {}, np.ones((height, width), bool)
    for name in variables(node):
        env[name], ok = mosaic(per_var[name], bbox, crs, width, height,
                               method)
        valid &= ok
    value = np.broadcast_to(evaluate(node, env), (height, width))
    valid &= np.isfinite(value)
    return np.where(valid, value, 0.0), valid


def render_byte(text, per_var, bbox, crs, width, height, method, offset,
                scale, clip):
    """(height, width) uint8, 255 = no data: what the paletted PNG's
    pixels index the colour table with."""
    value, valid = render_plane(text, per_var, bbox, crs, width, height,
                                method)
    return scale_byte(value, valid, offset, scale, clip)


def palette(colours):
    """(256, 4) uint8 RGBA, from `utils/palette.go:27`
    `GradientRGBAPalette`'s description for an interpolated ramp: the
    n colours bound n - 1 sections of 256 // (n - 1) entries (the first
    256 mod (n - 1) sections hold one more); entry i of a section is
    its first colour plus i * (next - first) / section in integers,
    truncated toward zero, with the first colour's alpha.  Entry 255 is
    no data: fully transparent (`utils/ogc_encoders.go:82-142` writes it
    so)."""
    cols = [[int(c[k]) for k in "RGB"] + [int(c.get("A", 255))]
            for c in colours]
    bins = len(cols) - 1
    section, bonus = divmod(256, bins)
    ramp = []
    for s in range(bins):
        a, b = np.array(cols[s]), np.array(cols[s + 1])
        for i in range(section + (s < bonus)):
            rgb = a[:3] + np.trunc(i * (b[:3] - a[:3]) / section).astype(int)
            ramp.append(list(rgb % 256) + [a[3]])
    ramp = np.array(ramp, np.uint8)
    ramp[255] = 0
    return ramp


def compare(got, want):
    """What a check records of a served byte plane against the
    reference: the share of pixels that differ, the share whose
    validity differs, and the largest difference in levels where both
    hold data."""
    both = (got != 255) & (want != 255)
    diff = np.abs(got.astype(int) - want.astype(int))
    return {"mismatch": float(np.mean(got != want)),
            "validity_mismatch": float(np.mean((got != 255)
                                               != (want != 255))),
            "max_byte_diff": int(diff[both].max()) if both.any() else 0}
