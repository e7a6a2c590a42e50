"""Share of the slots a decode step advanced that were live, from the
engine's per-dispatch counter events."""


def read(ctx):
    name = ctx["spec"]["params"]["event"]
    live = advanced = 0
    for r in ctx["spans"]:
        if r["name"] != name:
            continue
        attrs = r.get("attrs") or {}
        live += int(attrs.get("slots_live", 0))
        advanced += int(attrs.get("slots_advanced", 0))
    if advanced <= 0:
        return None
    return 100.0 * live / advanced
