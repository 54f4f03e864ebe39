"""Driver `train_loop`: a ring of host batches from the seed, fed through
the system's own step call every step, so feed staging is inside the
window. The first steps of set-up go through the same call on the same
object and are what `correct` compares; the loss is read every
`read_loss_every`-th step and at the end, which also keeps the host from
running more than that far ahead of the device."""
import time

import numpy as np

from benchmark import stats, trace, traffic as traffic_mod

CHECK_STEPS = 3


def _feed(batch):
    return {"input_ids": batch[0], "mlm_labels": batch[1]}


def warm(run, sut):
    ring = traffic_mod.train_ring(run.traffic, run.seed,
                                  sut.model["vocab_size"], run.chips)
    run.obs["ring"] = ring
    losses = []
    for i in range(CHECK_STEPS):
        losses.append(float(np.asarray(sut.step(_feed(ring[i])))))
        run.mark("step %d" % (i + 1))
        if i == 0:
            grad_norm = sut.first_gradient_norms()
    run.obs["first_steps"] = {"loss": losses, "grad_norm": grad_norm,
                              "change_norm": sut.change_norms()}
    run.obs["first_batches"] = ring[:CHECK_STEPS]
    # the rest of the ring once: every buffer the window cycles through
    for batch in ring[CHECK_STEPS:]:
        last = sut.step(_feed(batch))
    np.asarray(last)


def window(run, sut):
    import jax

    ring = run.obs["ring"]
    every = int(run.traffic["read_loss_every"])
    capture = None
    if run.trace:
        capture = trace.Capture(run.out_dir, 0.4 * run.seconds,
                                min(3.0, 0.3 * run.seconds))
        capture.start()
    dispatch_s, steps, loss, syncs = [], 0, None, []
    t0 = run.obs["window_t0"] = time.monotonic()
    t_end = t0 + run.seconds
    while time.monotonic() < t_end:
        feed = _feed(ring[steps % len(ring)])
        with jax.profiler.TraceAnnotation("bench.exe_run"):
            t = time.monotonic()
            loss = sut.step(feed)
            dispatch_s.append(time.monotonic() - t)
        steps += 1
        if steps % every == 0:
            with jax.profiler.TraceAnnotation("bench.read_loss"):
                np.asarray(loss)
            syncs.append(time.monotonic())
    with jax.profiler.TraceAnnotation("bench.final_sync"):
        final = float(np.asarray(jax.block_until_ready(loss)))
    elapsed = time.monotonic() - t0
    tokens = steps * ring[0][0].size
    run.obs.update(attempted=steps, failed=0 if np.isfinite(final) else steps,
                   steps=steps, window_s=elapsed, tokens=tokens,
                   tokens_per_step=ring[0][0].size, dispatch_s=dispatch_s,
                   final_loss=final)
    # how evenly the window ran: the time between loss reads, which tells
    # a single stall from a run that was slow throughout
    between = [b - a for a, b in zip([t0] + syncs, syncs)]
    run.note("%d steps in %.3f s, final loss %.4f; seconds per %d steps: "
             "min %.3f median %.3f max %.3f"
             % (steps, elapsed, final, every, min(between, default=0),
                sorted(between)[len(between) // 2] if between else 0,
                max(between, default=0)))
    if capture is not None:
        run.obs["trace"] = capture.finish()
        if capture.error:
            run.note("trace: " + capture.error)
    return {"train_tokens_per_s": stats.rate(tokens, elapsed)}
