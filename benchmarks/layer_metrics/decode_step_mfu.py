"""The whole decode step's share of the chip's peak (its roofline)."""


def read(ctx):
    params, work = ctx["spec"]["params"], ctx["work"]
    seconds, calls = ctx["trace"].module_seconds(params["module_contains"])
    steps = work["decode_steps"]
    if calls == 0 or not steps or seconds <= 0:
        return None
    shapes = ctx["cell"].shapes_module(params["shapes"])
    least = sum(
        shapes.least_decode_step_seconds(
            work["model"], lens, work["page_size"], ctx["peaks"]
        )["least_s"]
        for lens in steps
    )
    # the stamps may see a step more or fewer than the device ran in the
    # window: hold the least time to the steps the device ran
    least *= calls / len(steps)
    return 100.0 * least / seconds
