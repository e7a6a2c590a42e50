"""The cascade decode kernel's share of its roofline over the traced
window: the larger of its operations over the bf16 peak and its bytes over
HBM, over the device time of the ops inside the decode-step program whose
name or HLO text holds one of ``params.match``.

- Operations: the absorbed kernel's ``2 c H (R + r)`` a slot a layer over
  the contexts the run saw (``shapes.decode_kernel_ops``); the cascade
  does not change them.
- Bytes: ``2 R`` a row of the ``decode_mla_rows`` events'
  ``rows_fetched`` (a group's shared run once a step, each slot's rows
  past it, every layer), and each live slot's query in and latent average
  out (``shapes.decode_kernel_bytes`` over no rows).

Each sum is held to the steps the device ran in the window, as
``mla_kernel_roofline`` holds its own. The larger of two sums is at most
the sum of each step's larger, so the share can read low, never high. A
program without the kernel, or whose events carry no ``rows_fetched``,
gives nothing to read."""


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
    fetched = [
        r["attrs"]["rows_fetched"] for r in ctx["spans"]
        if r["name"] == "decode_mla_rows" and "rows_fetched" in (r.get("attrs") or {})
    ]
    steps = work.get("decode_steps") or []
    if not fetched or not steps:
        return None
    shapes = ctx["cell"].shapes_module(params["shapes"])
    model = work["model"]
    _, ran = ctx["trace"].module_seconds(module)
    per_step = ran or len(steps)
    ops = sum(shapes.decode_kernel_ops(model, c) for lens in steps for c in lens)
    slot_bytes = sum(len(lens) for lens in steps) * shapes.decode_kernel_bytes(model, 0)
    row_bytes = 2.0 * shapes.latent_row(model) * sum(fetched)
    least = max(
        ops * per_step / len(steps) / peaks["bf16_flops_per_s"],
        (
            slot_bytes * per_step / len(steps)
            + row_bytes * per_step / len(fetched)
        ) / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds
