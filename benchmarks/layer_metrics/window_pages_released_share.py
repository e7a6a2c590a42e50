"""Share of the window layer group's allocated pages that were handed back
by the release rule (wholly behind ``length - window``), from the
scheduler's per-iteration counter events."""


def read(ctx):
    params = ctx["spec"]["params"]
    group = params["group"]
    released = allocated = 0
    for r in ctx["spans"]:
        attrs = r.get("attrs") or {}
        if r["name"] == params["released_event"]:
            released += int(attrs.get(group, 0))
        elif r["name"] == params["allocated_event"]:
            allocated += int(attrs.get(group, 0))
    if allocated <= 0:
        return None
    return 100.0 * released / allocated
