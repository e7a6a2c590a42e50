"""A state-space kernel's share of its roofline, the kernel found by name:
``params.kind`` ``decode`` (the live sequences' state once in and once out
a step, over HBM bandwidth) or ``prefill`` (per prompt the larger of the
chunked scan's operations over the bf16 peak and its bytes over HBM), over
the device time of the ops inside ``params.module_contains`` whose name or
HLO text holds one of ``params.match``. A program without such an op (one
that predates the kernels) gives nothing to read."""


def read(ctx):
    params, work, peaks = ctx["spec"]["params"], ctx["work"], ctx["peaks"]
    needles, module = params["match"], params["module_contains"]

    def match(name, stats):
        text = name + " " + stats.get("text", "")
        return any(n in text for n in needles)

    seconds, calls = ctx["trace"].op_seconds(match, within_module=module)
    if calls == 0 or seconds <= 0:
        return None
    shapes = ctx["cell"].shapes_module(params["shapes"])
    model = work["model"]
    if params["kind"] == "decode":
        steps = work["decode_steps"]
        if not steps:
            return None
        nbytes = sum(shapes.ssm_step_bytes(model, len(lens)) for lens in steps)
        # the stamps may see a step more or fewer than the device ran in
        # the window: hold the needed bytes to the steps the device ran
        _, ran = ctx["trace"].module_seconds(module)
        if ran:
            nbytes *= ran / len(steps)
        return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds
    cold = [n for n, cached in work["prefills"] if cached == 0]
    if not cold:
        return None
    least = sum(shapes.least_ssm_scan_seconds(model, n, peaks) for n in cold)
    return 100.0 * least / seconds
