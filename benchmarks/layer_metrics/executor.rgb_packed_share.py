"""Share of three-band tiles served by the one-granule kernel
(`/debug` rgb_routes: `rgba` over `rgba` + `planes` + `fallback`; a
tile over no granule is counted `empty` there and is no part of this).
None from a program whose `/debug` has no `rgb_routes`."""

from benchmarks.ctx import dig

ROUTES = ["rgb_routes.rgba", "rgb_routes.planes", "rgb_routes.fallback"]


def read(ctx):
    if dig(ctx.debug1, "rgb_routes", None) is None:
        return None
    return ctx.ratio(ROUTES[:1], ROUTES, 100.0)
