"""Entry kind ``serve``: requests through the program's own serving path,
``LMServingConfig.build_service()`` -> ``DecodeScheduler.submit`` with its
worker thread running, tokens read from each ``DecodeStream`` as they
arrive.

The configuration's file gives the model's sizes and the keys the service
is configured with; the traffic file gives arrivals, lengths and sharing
(``zkbench/traffic.py``). Nothing here names a model or a mix.

One driver thread (this one) does everything the clients do: it submits
each request when it is due (open loop) or when its client's last request
completed (closed loop), and it looks at every live stream about once a
millisecond and stamps each new token with the host's clock. Latencies are
therefore the client's: time to first token runs from the moment a request
was *due*, so the wait a stall imposes on later requests is counted, and
how late the driver itself ran is reported.

The model's weights are the benchmark's own (``zkbench/weights.py``),
handed to the service through its model component's ``initialize``.

- time to first token (printed in every run as ``client view``, the mean,
  the median and the 90th and 95th percentiles; not a metric of a cell:
  over the 78 requests of a 30 s window neither the tail nor the mean
  keeps within a bound of 0.1 from seed to seed, PERF.md section 2): over
  every request due in the window, first token minus due time; a request
  that failed or never answered counts with the whole wait until the run
  gave up on it.
- ``itl_p95_ms``: 95th percentile over every gap between consecutive
  tokens of every request, the later of the two delivered inside the
  window.
- ``serve_tokens_per_s``: prompt tokens whose prefill completed inside the
  window plus output tokens delivered inside it, over the window's seconds.
"""

import gc
import os
import time
from typing import Any, Dict, List

import numpy as np

from zkbench import compare, spans, tracereduce, traffic
from zkbench.cells import merged
from zkbench.device import memory_peak_bytes
from zkbench.weights import make_weights, seed32


class Record:
    """One request as its client saw it (times: ``perf_counter``)."""

    __slots__ = (
        "request", "due_t", "submit_t", "stream", "token_t", "done_t",
        "error",
    )

    def __init__(self, request, due_t):
        self.request = request
        self.due_t = due_t
        self.submit_t = None
        self.stream = None
        self.token_t: List[float] = []
        self.done_t = None
        self.error = None


class Driver:
    """Submits requests and stamps tokens; one thread, no wall-clock in the
    traffic."""

    POLL_S = 0.001

    def __init__(self, scheduler):
        self.scheduler = scheduler
        self.live: List[Record] = []
        self.records: List[Record] = []
        #: (time, [context length of each slot that decoded]) per decode
        #: step, as the stamps show them: tokens after a stream's first
        #: that land in one look belong to one step.
        self.decode_steps: List[Any] = []
        self.late_s: List[float] = []

    def submit(self, record: Record, now: float) -> None:
        request = record.request
        record.submit_t = now
        try:
            record.stream = self.scheduler.submit(
                request.prompt, max_new_tokens=request.max_new_tokens
            )
        except Exception as e:  # refused or shed: it counts as failed
            record.error = e
            record.done_t = now
            self.records.append(record)
            return
        self.live.append(record)
        self.records.append(record)

    def look(self, now: float) -> List[Record]:
        """Stamp new tokens; returns the records that finished."""
        finished, step = [], []
        for record in self.live:
            stream = record.stream
            n = len(stream._tokens)  # read-only peek; see PERF.md section 7
            seen = len(record.token_t)
            if n > seen:
                prompt = len(record.request.prompt)
                for j in range(seen, n):
                    record.token_t.append(now)
                    if j > 0:
                        step.append(prompt + j)
            if stream.done and len(stream._tokens) == len(record.token_t):
                record.done_t = now
                record.error = stream.error
                finished.append(record)
        if step:
            self.decode_steps.append((now, step))
        if finished:
            self.live = [r for r in self.live if r.done_t is None]
        return finished

    # -- the two loops ---------------------------------------------------

    def run_open(self, requests, t0: float, seconds: float, hook=None):
        pending = list(requests)
        i = 0
        while True:
            now = time.perf_counter()
            if hook is not None:
                hook(now)
            while i < len(pending) and t0 + pending[i].due_s <= now:
                record = Record(pending[i], t0 + pending[i].due_s)
                self.late_s.append(now - record.due_t)
                self.submit(record, now)
                i += 1
            self.look(time.perf_counter())
            if now >= t0 + seconds:
                break
            nxt = t0 + pending[i].due_s if i < len(pending) else t0 + seconds
            time.sleep(max(0.0, min(self.POLL_S, nxt - time.perf_counter())))

    def run_closed(self, per_client, t0: float, seconds: float, hook=None):
        cursor = [0] * len(per_client)

        def send(client, now):
            queue = per_client[client]
            if cursor[client] >= len(queue):
                raise RuntimeError("closed loop ran out of generated requests")
            record = Record(queue[cursor[client]], now)
            cursor[client] += 1
            self.submit(record, now)

        now = time.perf_counter()
        for client in range(len(per_client)):
            send(client, now)
        while True:
            now = time.perf_counter()
            if hook is not None:
                hook(now)
            if now >= t0 + seconds:
                break
            for record in self.look(now):
                send(record.request.client, now)
            time.sleep(self.POLL_S)

    def drain(self, give_up_at: float) -> None:
        while self.live and time.perf_counter() < give_up_at:
            self.look(time.perf_counter())
            time.sleep(self.POLL_S)
        now = time.perf_counter()
        for record in self.live:
            record.done_t = now
            record.error = record.stream.error or TimeoutError("never answered")
        self.live = []


def _model_component(box: Dict):
    import jax

    from zookeeper_tpu import component
    from zookeeper_tpu.models.transformer import TransformerLM

    @component
    class SeededTransformerLM(TransformerLM):
        """The program's model with the benchmark's weights."""

        def initialize(self, module, input_shape, seed: int = 0):
            import jax.numpy as jnp

            dummy = jnp.zeros((1, *input_shape), jnp.int32)
            shapes = jax.eval_shape(
                lambda: module.init(jax.random.PRNGKey(0), dummy, training=False)
            )
            shapes = dict(shapes)
            params_like = shapes.pop("params")
            box["params_like"] = params_like
            box["params"] = make_weights(params_like, box["seed"])
            state = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes
            )
            return box["params"], state

    return SeededTransformerLM


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(ctx) -> Dict[str, Any]:
    import jax

    cell = ctx.cell
    config, mix = cell.config, cell.traffic
    if ctx.rehearse:
        config = merged(config, config.get("rehearsal"))
        mix = merged(mix, mix.get("rehearsal"))
    model = config["model"]
    vocab = int(model["vocab_size"])
    seconds = float(ctx.seconds)

    from zookeeper_tpu import configure
    from zookeeper_tpu.serving import LMServingConfig

    box: Dict[str, Any] = {"seed": ctx.seed}
    service = LMServingConfig()
    conf = dict(config["program"])
    conf.update({
        "model": _model_component(box),
        "seed": seed32(ctx.seed),
        "requests": 0,
        "verbose": False,
        "scheduler.synchronous": False,
    })
    configure(service, conf)
    ctx.phase("configured")
    engine, scheduler = service.build_service()
    ctx.phase("service built and warmed")
    notes: List[str] = []
    try:
        flavor = engine.decode_attention_flavor
        expected = config.get("expect", {}).get("decode_attention_flavor")
        if expected and not ctx.rehearse and flavor != expected:
            raise RuntimeError(
                f"decode_attention resolved to {flavor!r}, not {expected!r}"
            )
        driver = Driver(scheduler)
        closed = mix["arrivals"]["process"] == "closed"

        # Warm-up: every program runs once, the shared prefix is cached.
        warm = Driver(scheduler)
        for request in traffic.warmup_requests(mix, ctx.seed, vocab):
            warm.submit(Record(request, time.perf_counter()), time.perf_counter())
        warm.drain(time.perf_counter() + 300.0)
        bad = [r for r in warm.records if r.error is not None]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0].error!r}")

        if ctx.sweep_rates:
            sweep(ctx, scheduler, mix, vocab, seconds)
            raise SystemExit(0)
        if closed:
            rounds = int(mix.get("rounds", 64))
            schedule = traffic.closed_loop(mix, ctx.seed, rounds, vocab)
        else:
            schedule = traffic.open_loop(mix, ctx.seed, seconds, vocab)

        tracer = _Tracer(ctx, mix, seconds) if ctx.trace else None
        if ctx.trace:
            spans.enable()
            tracer.start()
            ctx.phase("profiler started")
            settle = Driver(scheduler)
            for request in traffic.warmup_requests(mix, ctx.seed + 1, vocab)[:1]:
                settle.submit(Record(request, time.perf_counter()), time.perf_counter())
            settle.drain(time.perf_counter() + 300.0)
        ctx.phase("window opens")
        compiles_at_open = ctx.compile_clock.compiles
        recompiles_at_open = engine.recompiles_detected
        t0 = time.perf_counter()
        setup_s = ctx.clock.since_start(t0)
        hook = tracer.hook(t0) if tracer else None
        run_for = tracer.run_for if tracer else seconds
        if closed:
            driver.run_closed(schedule, t0, run_for, hook)
        else:
            driver.run_open(schedule, t0, run_for, hook)
        t1 = t0 + run_for
        ctx.phase("window closed")
        if tracer:
            tracer.stop()
            ctx.phase("profiler stopped")
        driver.drain(t1 + float(mix.get("drain_seconds", 60)))
        ctx.phase("drained")
        compiled_inside = (
            ctx.compile_clock.compiles - compiles_at_open
            + engine.recompiles_detected - recompiles_at_open
        )
        peak, note = memory_peak_bytes(cell.chips, rehearse=ctx.rehearse)
        notes.append(note)
        records_host = spans.drain() if ctx.trace else []
        page_size = int(engine.page_size)
    finally:
        service._teardown_service(suppress=True)

    records = driver.records
    failed = [r for r in records if r.error is not None or not r.token_t]
    ttft, itl = [], []
    prompt_tokens = output_tokens = 0
    for r in records:
        if r.token_t:
            ttft.append(r.token_t[0] - r.due_t)
            # a gap belongs to the window if the token that ends it was
            # delivered inside it: the drain after the close, when nothing
            # arrives any more, is not what a user of the loaded server sees
            stamps = [t for t in r.token_t if t <= t1]
            itl.extend(np.diff(stamps).tolist())
            if r.token_t[0] <= t1:
                prompt_tokens += len(r.request.prompt)
            output_tokens += sum(1 for t in r.token_t if t <= t1)
        else:
            ttft.append((r.done_t or t1) - r.due_t)
    end_to_end = {
        "setup_s": setup_s,
        "serve_tokens_per_s": (prompt_tokens + output_tokens) / run_for,
    }
    if ttft:
        end_to_end["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
        end_to_end["ttft_p90_ms"] = 1e3 * percentile(ttft, 90)
        end_to_end["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
        end_to_end["ttft_mean_ms"] = 1e3 * float(np.mean(ttft))
    if itl:
        end_to_end["itl_p95_ms"] = 1e3 * percentile(itl, 95)
        end_to_end["itl_p50_ms"] = 1e3 * percentile(itl, 50)
        end_to_end["itl_mean_ms"] = 1e3 * float(np.mean(itl))
    shared = sum(int(r.stream.shared_tokens) for r in records if r.stream is not None)
    notes.append(
        f"window {run_for:.3f}s, {len(records)} requests "
        f"({len(failed)} failed), prompt tokens {prompt_tokens}, output "
        f"tokens {output_tokens}, served from prefix cache {shared}, "
        f"decode steps seen {len(driver.decode_steps)}, compiles inside "
        f"the window: {compiled_inside}, decode attention {flavor}"
    )
    notes.append(
        "client view: " + " ".join(
            f"{k}={v:.3f}" for k, v in sorted(end_to_end.items())
        )
    )
    if driver.late_s:
        notes.append(
            f"the generator ran late by p50 {1e3 * percentile(driver.late_s, 50):.3f} ms, "
            f"p99 {1e3 * percentile(driver.late_s, 99):.3f} ms, "
            f"max {1e3 * max(driver.late_s):.3f} ms over {len(driver.late_s)} requests"
        )

    # The sample for the check, drawn from the seed, the longest in it.
    finished = [r for r in records if r.error is None and r.token_t]
    sample = _sample(finished, int(mix.get("check_requests", 6)), ctx.seed)
    sequences = [
        {"prompt": r.request.prompt, "served": r.stream.tokens_so_far}
        for r in sample
    ]
    layer_ctx = None
    if ctx.trace:
        layer_ctx = _layer_ctx(
            ctx, cell, config, tracer, records, driver, records_host,
            page_size, shared,
        )

    # Free the program's state before the reference takes the chip.
    params, params_like = box.pop("params"), box.pop("params_like")
    for r in records:
        r.stream = None
    del service, engine, scheduler, driver, warm, box
    gc.collect()
    ctx.phase("program freed")

    reference = cell.reference_module()
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        found = reference.served_token_gaps(
            params, model, sequences, int(model["n_positions"]),
            lowp_control=ctx.with_control,
        )
    notes.append(
        f"reference: {time.perf_counter() - t:.1f}s over {len(sequences)} "
        f"requests: {found}"
    )
    ctx.phase("checked")
    limits = config.get("limits", {})
    values = {"served_logit_gap": found["widest_gap"]}
    correct, compared = compare.judge(values, limits)
    controls = {}
    if ctx.with_control:
        # the control in the program's place: at the same positions, the
        # token the float8 pass puts first, judged by the same limit
        verdict, rows = compare.judge(
            {"served_logit_gap": found["control_widest_gap"]}, limits
        )
        controls["control_fp8"] = {"correct": verdict, "compared": rows}
    if found["tokens_compared"] == 0:
        correct = False
    if failed:
        correct = False
        notes.append(f"{len(failed)} requests failed: {failed[0].error!r}")
    if compiled_inside:
        correct = False

    outcome = {
        "correct": correct,
        "attempted": len(records),
        "failed": len(failed),
        "end_to_end": end_to_end,
        "memory_peak_bytes": peak,
        "compared": compared,
        "controls": controls,
        "notes": notes,
        "counts": {
            "requests": len(records), "prompt_tokens": prompt_tokens,
            "output_tokens": output_tokens, "prefix_tokens": shared,
            "tokens_compared": found["tokens_compared"],
        },
    }
    if layer_ctx is not None:
        outcome["layer_ctx"] = layer_ctx
    return outcome


def sweep(ctx, scheduler, mix, vocab, seconds):
    """Offer each rate for ``seconds`` in this one process and print what
    it gave: the knee is the highest rate whose backlog does not grow (the
    second half of the window waits no longer than the first)."""
    for i, rate in enumerate(ctx.sweep_rates):
        at_rate = merged(mix, {"arrivals": {"process": "poisson", "rate_per_s": rate}})
        requests = traffic.open_loop(at_rate, ctx.seed + i, seconds, vocab)
        driver = Driver(scheduler)
        t0 = time.perf_counter()
        driver.run_open(requests, t0, seconds)
        backlog = len(driver.live)
        driver.drain(t0 + seconds + 60.0)
        drained_s = time.perf_counter() - t0 - seconds
        half = t0 + seconds / 2
        first = [r.token_t[0] - r.due_t for r in driver.records if r.token_t and r.due_t < half]
        second = [r.token_t[0] - r.due_t for r in driver.records if r.token_t and r.due_t >= half]
        itl = [g for r in driver.records for g in np.diff(r.token_t).tolist()]
        batch = [len(lens) for _, lens in driver.decode_steps]
        print(
            f"benchmark: sweep rate {rate:g}/s: {len(driver.records)} requests, "
            f"{sum(1 for r in driver.records if r.error is not None)} failed, "
            f"in flight at close {backlog}, drained in {drained_s:.2f}s, "
            f"ttft p50/p95 first half {1e3 * percentile(first, 50):.1f}/"
            f"{1e3 * percentile(first, 95):.1f} ms, second half "
            f"{1e3 * percentile(second, 50):.1f}/{1e3 * percentile(second, 95):.1f} ms, "
            f"itl p50/p95 {1e3 * percentile(itl, 50):.2f}/{1e3 * percentile(itl, 95):.2f} ms, "
            f"decode batch mean {np.mean(batch):.1f} max {max(batch)}, "
            f"late max {1e3 * max(driver.late_s):.2f} ms",
            flush=True,
        )


def _sample(finished: List[Record], k: int, seed: int) -> List[Record]:
    if not finished:
        return []
    longest = max(
        finished, key=lambda r: len(r.request.prompt) + len(r.token_t)
    )
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([seed32(seed), 5])
    picks = rng.permutation(len(rest))[: max(0, k - 1)]
    return [longest] + [rest[i] for i in picks]


class _Tracer:
    """The traced slice of a ``--trace 1`` run. The profiler is started
    during set-up, before the window opens, and one more warm-up request is
    served under it: the profiler's start stalls the next dispatch for
    seconds, and that stall must not fall into the window. The traced
    slice runs from ``trace_lead_seconds`` into the window for
    ``trace_seconds``; the run then ends (its end-to-end numbers are not
    reported)."""

    def __init__(self, ctx, mix, seconds):
        self.dir = os.path.join(ctx.out_dir, "trace")
        lead = float(mix.get("trace_lead_seconds", 0.0))
        span = float(mix.get("trace_seconds", 5.0))
        if lead + span > seconds:
            lead = max(0.0, seconds - span)
        self.lead, self.span = lead, min(span, seconds)
        self.run_for = self.lead + self.span
        self.marks: Dict[str, int] = {}
        self.stopped = False

    def start(self):
        tracereduce.start_profiler(self.dir)

    def hook(self, t0):
        def on_look(now):
            if "window_start" not in self.marks and now >= t0 + self.lead:
                self.marks["window_start"] = tracereduce.mark("window_start")

        return on_look

    def stop(self):
        if not self.stopped:
            self.marks["window_end"] = tracereduce.mark("window_end")
            tracereduce.stop_profiler()
            self.stopped = True


def _layer_ctx(ctx, cell, config, tracer, records, driver, host, page_size, shared):
    extract = tracereduce.extract_xplane(tracer.dir, host_fallback=ctx.rehearse)
    ctx.keep_extract(extract)
    trace = tracereduce.DeviceTrace(
        extract, chips=cell.chips, mark_host_ns=tracer.marks
    )
    lo, hi = tracer.marks["window_start"], tracer.marks["window_end"]
    lo_s, hi_s = lo / 1e9, hi / 1e9  # perf_counter and perf_counter_ns agree
    prefilled = [
        r for r in records if r.token_t and lo_s <= r.token_t[0] < hi_s
    ]
    outputs = sum(
        1 for r in records for t in r.token_t if lo_s <= t < hi_s
    )
    return {
        "trace": trace,
        "spans": spans.within(host, lo, hi),
        "window_host_ns": (lo, hi),
        "counters": {
            "prompt_tokens": sum(len(r.request.prompt) for r in records),
            "prefix_tokens": shared,
        },
        "work": {
            "model": config["model"],
            "page_size": page_size,
            "decode_steps": [
                lens for t, lens in driver.decode_steps if lo_s <= t < hi_s
            ],
            "prefills": [
                (len(r.request.prompt), int(r.stream.shared_tokens))
                for r in prefilled
            ],
            "output_tokens": outputs,
            "output_contexts": [
                n for t, lens in driver.decode_steps if lo_s <= t < hi_s
                for n in lens
            ],
        },
    }
