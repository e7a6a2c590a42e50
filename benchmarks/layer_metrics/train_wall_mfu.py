"""The training loop's share of the chip's peak on the host's clock: the
least compute time a step's operations need times the steps per second of
the run's untraced slice, the loader's and the loop's waits included. It is
``train_throughput`` against the peak, not a device reading; the device's
own share is ``train_step_mfu``, and one minus the ratio of the two is the
share of the loop's time in which the device sat idle."""


def read(ctx):
    work, trace = ctx["work"], ctx["trace"]
    rate = work.get("steps_per_s_untraced", 0)
    if rate <= 0:
        return None
    steps, busy = work.get("steps", 0), trace.busy_s()
    # The device cannot be busy for more than the loop's time a step. Two
    # slices of one run may differ by a few percent once the loop is bound
    # by the device (the 105% the driver allows a share of a peak); more
    # says that they do not count the same steps. A rehearsal's stand-in
    # trace is host threads, which overlap: it is not held to this.
    if steps > 0 and not work.get("rehearsal") and busy / steps * rate > 1.05:
        raise ValueError(
            f"the device was busy {busy / steps:.4f}s a step in the traced "
            f"slice, but the untraced slice made {rate:.3f} steps a second: "
            "the two slices do not count the same steps"
        )
    shapes = ctx["cell"].shapes_module(ctx["spec"]["params"]["shapes"])
    least = shapes.least_step_seconds(
        work["model"], work["items_per_step"] // work["chips"], ctx["peaks"]
    )
    return 100.0 * least["compute_s"] * rate
