"""Mean milliseconds a scheduler iteration spent neither running nor
waiting for the device, over the iterations that lie whole in the traced
window.

While tracing, the scheduler closes an iteration with one event
(``params.closing_event``) that carries the iteration's ``wall_ns`` and its
thread's ``cpu_ns``. The readback waits are the program's own too: of each
dispatch span of the iteration (a name that ends in ``params.span_suffix``)
the part after its ``params.boundary_event``, in which the thread waits for
the device and reads back. ``wall_ns - cpu_ns - waits`` is what is left:
the interpreter's lock, the scheduler's own lock, the machine's other
threads. An iteration that began before the window is left out (its
dispatch spans are not all among the records). A program that puts no
``wall_ns`` on the event gives ``None``."""


def read(ctx):
    params = ctx["spec"]["params"]
    lo, _ = ctx["window_host_ns"]
    waits, last, stalls = {}, {}, []
    # in the order the ring holds them: a span closes after its events
    for r in sorted(ctx["spans"], key=lambda r: r["ts_ns"] + r["dur_ns"]):
        key = (r["thread_id"], r["step"])
        if r["name"] == params["boundary_event"]:
            last[r["thread_id"]] = r["ts_ns"]
        elif r["phase"] == "X" and r["name"].endswith(params["span_suffix"]):
            at = last.pop(r["thread_id"], None)
            if at is not None and at >= r["ts_ns"]:
                waits[key] = waits.get(key, 0) + r["ts_ns"] + r["dur_ns"] - at
        elif r["name"] == params["closing_event"]:
            attrs, waited = r["attrs"] or {}, waits.pop(key, 0)
            if "wall_ns" in attrs and r["ts_ns"] - attrs["wall_ns"] >= lo:
                stalls.append(attrs["wall_ns"] - attrs["cpu_ns"] - waited)
    if not stalls:
        return None
    return sum(stalls) / len(stalls) / 1e6
