"""The page-pool decode kernel's share of its (bandwidth) roofline."""


def read(ctx):
    params, work = ctx["spec"]["params"], ctx["work"]
    target, module = params["target"], params["module_contains"]
    seconds, calls = ctx["trace"].op_seconds(
        lambda name, stats: stats.get("target") == target, within_module=module
    )
    steps = work["decode_steps"]
    if calls == 0 or seconds <= 0 or not steps:
        return None
    shapes = ctx["cell"].shapes_module(params["shapes"])
    nbytes = sum(
        shapes.live_kv_bytes(work["model"], lens, work["page_size"])
        for lens in steps
    )
    # the stamps may see a step more or fewer than the device ran in the
    # window: hold the needed bytes to the steps the device ran
    _, ran = ctx["trace"].module_seconds(module)
    if ran:
        nbytes *= ran / len(steps)
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / seconds
