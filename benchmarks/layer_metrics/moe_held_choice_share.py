"""Share of the routed (token, choice) pairs whose expert this chip holds,
from the engine's per-dispatch counter events."""


def read(ctx):
    name = ctx["spec"]["params"]["event"]
    held = routed = 0
    for r in ctx["spans"]:
        if r["name"] != name:
            continue
        attrs = r.get("attrs") or {}
        held += int(attrs.get("choices_held", 0))
        routed += int(attrs.get("choices_routed", 0))
    if routed <= 0:
        return None
    return 100.0 * held / routed
