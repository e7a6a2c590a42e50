"""Share of the routed (token, choice) pairs that went to an identity
(zero-computation) expert, from the engine's per-dispatch counter events. A
program without identity experts, or without the counter, gives nothing to
read."""


def read(ctx):
    name = ctx["spec"]["params"]["event"]
    zero = routed = 0
    for r in ctx["spans"]:
        if r["name"] != name:
            continue
        attrs = r.get("attrs") or {}
        zero += int(attrs.get("choices_zero", 0))
        routed += int(attrs.get("choices_routed", 0))
    if routed <= 0:
        return None
    return 100.0 * zero / routed
