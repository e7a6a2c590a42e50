"""The share of the latent rows the decode steps read that lie on pages
other slots or the prefix cache hold too: ``rows_shared`` over
``rows_read`` of the ``decode_mla_rows`` events (program counter). What a
kernel that read a shared page once a step, for every slot on it, would
no longer read."""


def read(ctx):
    read_rows = shared = 0
    for r in ctx["spans"]:
        if r["name"] == "decode_mla_rows":
            attrs = r.get("attrs") or {}
            read_rows += attrs.get("rows_read", 0)
            shared += attrs.get("rows_shared", 0)
    if read_rows <= 0:
        return None
    return 100.0 * shared / read_rows
