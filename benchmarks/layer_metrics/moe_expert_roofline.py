"""The grouped expert matmuls' share of their roofline: per call the larger
of compute and memory, over the ops' device time."""


def _matches(needles):
    def match(name, stats):
        text = name + " " + stats.get("text", "")
        return any(n in text for n in needles)

    return match


def read(ctx):
    params, work, peaks = ctx["spec"]["params"], ctx["work"], ctx["peaks"]
    seconds, calls = ctx["trace"].op_seconds(_matches(params["match"]))
    if calls == 0 or seconds <= 0:
        return None
    shapes = ctx["cell"].shapes_module(params["shapes"])
    model = work["model"]
    least = sum(
        shapes.least_expert_seconds(model, len(lens), peaks)
        for lens in work["decode_steps"]
    )
    least += sum(
        shapes.least_expert_seconds(model, n - cached, peaks)
        for n, cached in work["prefills"]
    )
    if least <= 0:
        return None
    return 100.0 * least / seconds
