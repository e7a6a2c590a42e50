"""A latent-attention kernel's share of its roofline, the kernel found by
name (the ops whose name or HLO text holds one of ``params.match``):
``params.kind`` ``decode`` (the absorbed decode kernel: per decode step the
larger of its operations over the bf16 peak and its bytes over HBM, the
contexts as the run saw them) or ``window`` (the prefill/extend kernel:
per prefilled prompt the larger of the causal expanded form's operations
and bytes for its new positions over its cached ones), over the kernel's
device time. Both from ``params.shapes``."""


def read(ctx):
    params, work, peaks = ctx["spec"]["params"], ctx["work"], ctx["peaks"]
    needles = params["match"]

    def match(name, stats):
        text = name + " " + stats.get("text", "")
        return any(n in text for n in needles)

    module = params.get("module_contains")
    seconds, calls = ctx["trace"].op_seconds(match, within_module=module)
    if calls == 0 or seconds <= 0:
        return None
    shapes = ctx["cell"].shapes_module(params["shapes"])
    model = work["model"]
    if params["kind"] == "decode":
        steps = work["decode_steps"]
        least = sum(
            shapes.least_decode_kernel_seconds(model, lens, peaks)
            for lens in steps
        )
        # the stamps may see a step more or fewer than the device ran in
        # the window: hold the needed work to the steps the device ran
        _, ran = ctx["trace"].module_seconds(module)
        if steps and ran:
            least *= ran / len(steps)
    else:
        least = sum(
            shapes.least_window_kernel_seconds(model, m, cached, peaks)
            for m, cached in work["prefills"]
        )
    if least <= 0:
        return None
    return 100.0 * least / seconds
