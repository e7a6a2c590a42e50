"""The share of the latent rows the decode steps read that the decode
kernel reads through a group's shared items: ``rows_cascaded`` over
``rows_read`` of the ``decode_mla_rows`` events (program counter). How
often the cascade engages. A program whose events carry no
``rows_cascaded`` (one without the cascade) gives nothing to read."""


def read(ctx):
    read_rows = cascaded = 0
    for r in ctx["spans"]:
        attrs = r.get("attrs") or {}
        if r["name"] == "decode_mla_rows" and "rows_cascaded" in attrs:
            read_rows += attrs.get("rows_read", 0)
            cascaded += attrs["rows_cascaded"]
    if read_rows <= 0:
        return None
    return 100.0 * cascaded / read_rows
