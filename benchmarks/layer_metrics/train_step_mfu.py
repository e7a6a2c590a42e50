"""The whole step's share of the chip's peak while the device works on it:
the least compute time the step's operations need over the device's busy
time per step, both inside the traced window. The profiler slows this
loop's host (see ``entries/train.py``), not the device's work on a step,
so this share is what the compiled step reaches; what the loop reaches
with the host's waits in it is ``train_wall_mfu``."""


def read(ctx):
    work, trace = ctx["work"], ctx["trace"]
    steps, busy = work.get("steps", 0), trace.busy_s()
    if steps <= 0 or busy <= 0:
        return None
    shapes = ctx["cell"].shapes_module(ctx["spec"]["params"]["shapes"])
    least = shapes.least_step_seconds(
        work["model"], work["items_per_step"] // work["chips"], ctx["peaks"]
    )
    return 100.0 * least["compute_s"] * steps / busy
