"""A training job: one dispatch per step for the whole window.

Traffic keys: `batch_per_chip`, `ring` (seeded input batches cycled
through), `loss_every` (the job's log line: the host reads the loss every
so many steps, which is also all that paces it), `warmup_steps`,
`warmup_loss_band` {step, min, max}, `check_items`, `trace` {steps,
synced_steps}, and the family's own (`dtype`, `lr`, ...).

No scan of steps in one program and no best-of: users dispatch a step at a
time, and a minimum hides stalls.
"""
from __future__ import annotations

import math
import time

import numpy as np

from .. import stats
from ..spans import traced_slice


def steady(job, spans, loss_every, until=None, steps=None):
    """Dispatch until the clock passes `until` (or for `steps` steps), then
    wait for the last step. Returns (losses, the instant the last one was
    done): the window ends when its work does, so no step is cut."""
    losses = []
    while True:
        losses.append(job.dispatch())
        if len(losses) % loss_every == 0:
            with spans("read_loss"):
                float(losses[-1])
        if (steps is not None and len(losses) >= steps) or \
                (until is not None and time.perf_counter() >= until):
            break
    with spans("read_loss"):
        losses[-1].block_until_ready()
    return losses, time.perf_counter()


def run(r):
    import jax.numpy as jnp
    mix = r.cell.traffic
    job = r.cell.family.TrainJob(r.cell, r.seed, r.spans)
    r.counters.add_source("", job.counters)
    r.log("job built")
    try:
        warm = [float(job.dispatch()) for _ in range(mix["warmup_steps"])]
        r.log(f"warm-up losses {[round(x, 4) for x in warm]}")
        check = job.check(mix["check_items"])
        r.log(f"against the reference: {check}")

        r.start_window()
        t0 = time.perf_counter()
        every = mix["loss_every"]
        driver, anchor = {}, None
        # a traced run: half the window as an untraced run goes, then steps
        # timed one by one, then a slice under the profiler
        losses, t1 = steady(job, r.spans, every, until=t0 + (
            r.seconds / 2 if r.trace else r.seconds))
        rate = len(losses) * job.items_per_step / (t1 - t0)
        if r.trace:
            step_ms = []
            for _ in range(mix["trace"]["synced_steps"]):
                s0 = time.perf_counter()
                loss = job.dispatch()
                loss.block_until_ready()
                step_ms.append((time.perf_counter() - s0) * 1e3)
                losses.append(loss)
            driver["step_p50_ms"] = stats.median(step_ms)
            (sliced, _), anchor = traced_slice(r.trace_dir, lambda: steady(
                job, r.spans, every, steps=mix["trace"]["steps"]))
            losses += sliced
        r.end_window()
        values = np.asarray(jnp.stack(losses), np.float64).tolist()
    finally:
        job.close()

    band = mix["warmup_loss_band"]
    at = warm[band["step"]]
    failed = sum(not math.isfinite(v) for v in values)
    built = r.counters.over("jax.programs_built", "window")
    problems = []
    if not check["ok"]:
        problems.append(f"logits differ from the reference: {check}")
    if not band["min"] <= at <= band["max"]:
        problems.append(f"loss {at} at warm-up step {band['step']} is "
                        f"outside the recorded band {band}")
    if failed:
        problems.append(f"{failed} non-finite losses")
    if built:
        problems.append(f"{built} programs were built inside the window")
    driver.update(items_per_s=rate, steps=len(values),
                  flops_per_item=r.cell.family.train_flops_per_item(
                      r.cell.config, mix),
                  last_loss=values[-1], check=check, warmup_losses=warm)
    return {"attempted": len(values), "failed": failed,
            "problems": problems, "e2e": {"train_items_per_s": rate},
            "driver": driver, "anchor": anchor}
