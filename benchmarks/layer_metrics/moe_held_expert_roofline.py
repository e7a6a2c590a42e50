"""The grouped matmuls' share of their roofline for a model that holds a
share of its experts, from the rows the device counted: per dispatch and
layer the larger of the held choices' operations over the bf16 peak and
the bytes of the held experts that got a row over HBM bandwidth
(``moe_tokens_per_expert`` events, ``counts [layers][held]``), over the
device time of the ops whose name or HLO text holds one of
``params.match``. Counted and not reckoned from the tokens: a seeded
router leaves some held experts without a row in a decode step, and
their matrices are not read."""


def read(ctx):
    params, peaks = ctx["spec"]["params"], ctx["peaks"]
    needles = params["match"]

    def match(name, stats):
        text = name + " " + stats.get("text", "")
        return any(n in text for n in needles)

    seconds, calls = ctx["trace"].op_seconds(match)
    if calls == 0 or seconds <= 0:
        return None
    shapes = ctx["cell"].shapes_module(params["shapes"])
    model = ctx["work"]["model"]
    ops = shapes.expert_ops_per_choice(model)
    least = 0.0
    for r in ctx["spans"]:
        if r["name"] != params["event"]:
            continue
        for layer in (r.get("attrs") or {}).get("counts", ()):
            touched = sum(1 for rows in layer if rows)
            least += max(
                sum(layer) * ops / peaks["bf16_flops_per_s"],
                shapes.expert_bytes(model, touched) / peaks["hbm_bytes_per_s"],
            )
    if least <= 0:
        return None
    return 100.0 * least / seconds
