"""The convolutions' share of their roofline, from the device trace."""


def read(ctx):
    work, trace = ctx["work"], ctx["trace"]
    params = ctx["spec"]["params"]
    kinds, starts = set(params["kinds"]), tuple(params["name_starts"])
    seconds, count = trace.op_seconds(
        lambda name, stats: stats.get("kind") in kinds
        or "convolution" in stats.get("hlo_category", "")
        or name.startswith(starts)
    )
    steps = work.get("steps", 0)
    if count == 0 or seconds <= 0 or steps <= 0:
        return None
    shapes = ctx["cell"].shapes_module(params["shapes"])
    least = shapes.least_step_seconds(
        work["model"], work["items_per_step"] // work["chips"], ctx["peaks"],
        convs_only=True,
    )
    return 100.0 * max(least["compute_s"], least["memory_s"]) * steps / seconds
