"""Online requests: single rows through `GBDTServer`'s deadline batcher,
sent in an open loop.

Set-up builds `GBDTServer(ensemble)` with its defaults, scores one batch
of every bucket size (each compiles), then offers `warm_seconds` of the
cell's traffic, unmeasured.  The window offers the traffic mix's
arrivals (bench/harness/schedule.py) for `--seconds`; each request is
one row drawn from the seed.

`GBDTServer.predict` is `batcher.submit` followed by a blocking wait on
the reply.  The client splits the two over two threads, so that one
sender keeps thousands of requests in flight: the sender submits each
request at its scheduled time, the receiver waits on the replies in
order and stamps each as it arrives.

    latency      reply time - scheduled send time: a request's wait
                 behind a late sender or a stalled server counts
    p50_ms       median latency over every request due in the window
    p99_ms       99th percentile (nearest rank) of the same set; a
                 request with no reply a minute after the window counts
                 as FAILED_MS, standing for infinity

Traffic parameters: "rate_per_s", "arrivals" (and their own keys),
"warm_seconds", "trace_seconds" (the traced slice after the window),
"check_rows" (replies compared with the reference).
"""
from __future__ import annotations

import gc
import queue
import threading
import time

import numpy as np

from harness import data, device, model, program, reference, schedule, trace
from harness.runner import Check, Outcome

GIVE_UP_S = 60.0
FAILED_MS = 1e9
STREAM_WARM, STREAM_TRACED, STREAM_ROWS = 11, 12, 100


def open_loop(batcher, payloads: np.ndarray, sched: np.ndarray):
    """Offer payloads[i] at sched[i] s; -> (latency s, late s, replies).

    Latency is NaN where no reply came within GIVE_UP_S of the last
    send time."""
    n = len(sched)
    got = np.full(n, np.nan)
    sent = np.full(n, np.nan)
    replies: list = [None] * n
    handoff: queue.SimpleQueue = queue.SimpleQueue()
    t_start = time.perf_counter() + 0.005
    deadline = t_start + (sched[-1] if n else 0.0) + GIVE_UP_S

    def receive():
        for _ in range(n):
            i, fut = handoff.get()
            try:
                replies[i] = fut.get(
                    timeout=max(deadline - time.perf_counter(), 1e-3))
            except queue.Empty:
                continue
            got[i] = time.perf_counter()

    receiver = threading.Thread(target=receive, name="bench-receiver")
    receiver.start()
    i = 0
    while i < n:
        ahead = sched[i] - (time.perf_counter() - t_start)
        if ahead > 0:
            time.sleep(ahead)
            continue
        now = time.perf_counter() - t_start
        while i < n and sched[i] <= now:
            handoff.put((i, batcher.submit(i, payloads[i])))
            sent[i] = time.perf_counter()
            i += 1
    receiver.join()
    due = t_start + sched
    return got - due, sent - due, replies


def _percentile_ms(latency: np.ndarray, q: float) -> float:
    """Nearest-rank percentile in ms; a missing reply ranks as FAILED_MS."""
    ms = np.where(np.isnan(latency), FAILED_MS, latency * 1e3)
    ms = np.sort(ms)
    return float(ms[max(int(np.ceil(q / 100.0 * len(ms))) - 1, 0)])


def _offer(batcher, x, tr, seconds, seed, stream):
    sched = schedule.arrivals(tr, seconds, seed, stream)
    rows = data.rng(seed, STREAM_ROWS + stream).integers(0, len(x),
                                                         len(sched))
    lat, late, replies = open_loop(batcher, x[rows], sched)
    return rows, lat, late, replies


def run(ctx) -> Outcome:
    import jax

    from repro.serving.engine import GBDTServer

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    x, _ = data.generate(cfg, ctx.seed)
    mdl = model.random_ensemble(cfg, x, ctx.seed)
    server = GBDTServer(model.to_program(mdl))
    try:
        for b in server.buckets:
            server.predict_batch(x[:b])
        _offer(server.batcher, x, tr, float(tr["warm_seconds"]), ctx.seed,
               STREAM_WARM)
        devices = jax.devices()[:ctx.cell.chips]
        m0 = server.metrics.snapshot()

        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_process0
        ctx.emit({"phase": "setup", "device": device.record(devices),
                  "plan": program.plan_record(server.predictor),
                  "impls": program.impls(),
                  "buckets": list(server.buckets), "setup_s": setup_s,
                  **ctx.clock.take()})
        rows, lat, late, replies = _offer(server.batcher, x, tr,
                                          ctx.seconds, ctx.seed,
                                          data.STREAM_ORDER)
        t_end = time.perf_counter()
        lat_window = lat
        in_window = ctx.clock.take()
        m1 = server.metrics.snapshot()
        batches = m1["batches"] - m0["batches"]
        served = m1["requests"] - m0["requests"]
        ctx.emit({"phase": "window", "requests": len(rows),
                  "seconds": t_end - t0, "batches": batches,
                  "send_late_p50_ms": float(np.nanmedian(late) * 1e3),
                  "send_late_p99_ms": float(np.nanpercentile(late, 99)
                                            * 1e3),
                  "compiles_in_window": in_window["compiles"]
                  + in_window["cache_hits"], **in_window})

        summary, traced_valid = None, 0
        if ctx.trace:
            q0 = server.metrics.snapshot()["requests"]
            with trace.capture(ctx.trace_out()) as found:
                t_rows, t_lat, _, t_replies = _offer(
                    server.batcher, x, tr, float(tr["trace_seconds"]),
                    ctx.seed, STREAM_TRACED)
            traced_valid = server.metrics.snapshot()["requests"] - q0
            summary = trace.summarize(trace.load(found[0]))
            rows = np.concatenate([rows, t_rows])
            lat = np.concatenate([lat, t_lat])
            replies = replies + t_replies
        peak = device.memory_peak_bytes(devices)
    finally:
        server.close()
    del server
    gc.collect()

    answered = np.flatnonzero(~np.isnan(lat))
    failed = len(lat) - len(answered)
    pick = answered[data.sample_rows(len(answered), int(tr["check_rows"]),
                                     ctx.seed)]
    want = reference.proba(reference.raw_f64(mdl, x[rows[pick]]))
    got = np.stack([replies[i] for i in pick]) if len(pick) else \
        np.zeros((0, want.shape[1]))
    return Outcome(
        e2e={"p50_ms": _percentile_ms(lat_window, 50),
             "p99_ms": _percentile_ms(lat_window, 99),
             "setup_s": setup_s},
        counters={"batches": batches, "requests": served,
                  "traced_valid_rows": traced_valid},
        checks={"proba_max_abs_err": Check(
                    reference.max_abs_err(got, want),
                    float(cfg["limits"]["proba_max_abs_err"])),
                "unanswered": Check(failed, 0)},
        attempted=len(lat), failed=failed, memory_peak_bytes=peak,
        trace=summary, traced_rows=traced_valid)
