"""Model operations of the traced window over the chip's bf16 peak."""


def read(ctx):
    work, trace = ctx["work"], ctx["trace"]
    if trace.window_s <= 0 or trace.busy_s() <= 0:
        return None
    shapes = ctx["cell"].shapes_module(ctx["spec"]["params"]["shapes"])
    model = work["model"]
    ops = sum(shapes.prompt_ops(model, n, cached) for n, cached in work["prefills"])
    ops += sum(shapes.output_token_ops(model, n) for n in work["output_contexts"])
    if ops <= 0:
        return None
    return 100.0 * ops / (ctx["peaks"]["bf16_flops_per_s"] * trace.window_s)
