"""Server time per Execute that no stage span covers: the root span's
age when the answer is ready, less every named stage (`/debug`
drill_stages.wall_s minus each `*_s` stage, over requests).  The stages
of one request run one after another, so this is thread hand-off, event
loop, GIL wait and the code between the spans."""

STAGES = ("parse_s", "admission_s", "index_s", "prepare_s", "device_s",
          "host_read_s", "merge_s", "format_s")


def read(ctx):
    if not ctx.delta("drill_stages.requests"):
        return None
    named = sum(ctx.delta(f"drill_stages.{k}") for k in STAGES)
    return 1e3 * (ctx.delta("drill_stages.wall_s") - named) \
        / ctx.delta("drill_stages.requests")
