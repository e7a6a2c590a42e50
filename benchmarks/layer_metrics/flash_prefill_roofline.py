"""The flash forward kernel's share of its (compute) roofline."""


def read(ctx):
    params, work = ctx["spec"]["params"], ctx["work"]
    target = params["target"]
    seconds, calls = ctx["trace"].op_seconds(
        lambda name, stats: stats.get("target") == target,
        within_module=params["module_contains"],
    )
    cold = [n for n, cached in work["prefills"] if cached == 0]
    if calls == 0 or seconds <= 0 or not cold:
        return None
    shapes = ctx["cell"].shapes_module(params["shapes"])
    ops = sum(shapes.prompt_attention_ops(work["model"], n) for n in cold)
    return 100.0 * ops / ctx["peaks"]["bf16_flops_per_s"] / seconds
