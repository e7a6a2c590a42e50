"""An attention kernel's share of its roofline, the kernel found by name:
``params.kind`` ``decode`` (the KV bytes the steps attend over HBM
bandwidth) or ``prefill`` (the prompts' attention operations over the bf16
peak), over the device time of the ops inside ``params.module_contains``
whose name or HLO text holds one of ``params.match``."""


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
        nbytes = sum(
            shapes.live_kv_bytes(model, lens, work["page_size"]) for lens in steps
        )
        # the stamps may see a step more or fewer than the device ran in
        # the window: hold the needed bytes to the steps the device ran
        _, ran = ctx["trace"].module_seconds(module)
        if ran:
            nbytes *= ran / len(steps)
        return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds
    cold = [n for n, cached in work["prefills"] if cached == 0]
    if not cold:
        return None
    ops = sum(shapes.prompt_attention_ops(model, n) for n in cold)
    return 100.0 * ops / peaks["bf16_flops_per_s"] / seconds
