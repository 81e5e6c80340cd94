"""Epoch orchestration: train → validate → checkpoint-best → early stop.

The reference's L6 (imagenet_ddp.py:200-324) with its exact console surface
(``Epoch: [e][i/N]  Time … Loss … Acc@1 …`` lines every ``--print-freq``,
``* Acc@1 … Acc@5 …`` validation summaries) and its control contract
(checkpoint-best each epoch, ``--desired-acc`` early stop recording
``training_time``, imagenet_ddp.py:224-236).

One deliberate performance change: metric scalars are NOT pulled from device
every step — device values are buffered and fetched once per print interval
(the reference's own optimization, imagenet_ddp_apex.py:385-388, applied to
all paths; its non-Apex path paid a ``.item()`` sync per batch,
imagenet_ddp.py:267).

The loop's run-ahead is bounded: before it dispatches step *i* it makes sure
that step *i* − ``MAX_IN_FLIGHT`` has landed, and waits for that one loss if
it has not. So the device always has a step running and the next queued
behind it, a device-bound run spends one device step in every iteration
(and not the whole queue's wait in the one iteration in ``--print-freq``
that fetches), and a preemption signal is answered within that many steps.
The display fetch lags by the same depth, so it reads only steps that have
landed. A run whose host sets the pace never has that many steps in flight,
finds the step landed and never waits.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np

from dptpu import obs
from dptpu.utils.meters import AverageMeter, ProgressMeter, Summary


# At most this many steps are in flight on the device at any time (the one
# running and those queued behind it). 2 is the depth the lagged display
# fetch drained the queue to before the run-ahead was bounded, so no worst
# case got worse; a constant, not a knob: the step time does not depend on
# it (one step lands per iteration at any depth), only the slack against a
# slow host iteration does (MAX_IN_FLIGHT - 1 device steps less one host
# iteration). PERF.md section 6, PR 28, has the readings that chose it.
MAX_IN_FLIGHT = 2


class MoeLoad:
    """The expert layers' load over the steps fetched so far, for a
    model whose step reports it (``moe_counts`` ``[layers, experts
    held]``, ``moe_slots``, ``moe_dropped``): per step and layer the
    tokens at the busiest held expert and at the mean one, the routed
    slots that fell on held experts, the tokens dropped. ``take`` gives
    what one fetch adds as the ``fetch`` span's attributes; ``stats``
    the epoch's averages. The step program's constants
    (``STEP_CONSTANTS``) are passed on as they are."""

    # the megabytes the model's blocks keep through their
    # rematerialisation; the attention's calls in one forward pass and
    # those of them that run as its kernels in this program; the same
    # of the state-space scan, and the chunks it walks a row in
    STEP_CONSTANTS = ("kept_residual_mb", "attention_calls",
                      "attention_kernel_calls", "ssd_calls",
                      "ssd_kernel_calls", "ssd_chunks")

    def __init__(self):
        self.steps = 0
        self.load_max = self.load_mean = 0.0
        self.local = self.slots = self.dropped = 0

    def take(self, fetched) -> dict:
        """``fetched``: the metrics of the steps one fetch read. Empty
        where the model has no experts."""
        kept = {key: int(m[key]) for m in fetched[-1:]
                for key in self.STEP_CONSTANTS if key in m}
        steps = [m for m in fetched if "moe_counts" in m]
        if not steps:
            return kept
        counts = [np.asarray(m["moe_counts"], np.float64) for m in steps]
        add = {
            **kept,
            "moe_load_max": float(np.mean([c.max(axis=1).mean()
                                           for c in counts])),
            "moe_load_mean": float(np.mean([c.mean() for c in counts])),
            "moe_local_slots": int(sum(c.sum() for c in counts)),
            "moe_slots": int(sum(int(m["moe_slots"]) for m in steps)),
            "moe_dropped": int(sum(int(m["moe_dropped"]) for m in steps)),
        }
        n = len(steps)
        self.steps += n
        self.load_max += add["moe_load_max"] * n
        self.load_mean += add["moe_load_mean"] * n
        self.local += add["moe_local_slots"]
        self.slots += add["moe_slots"]
        self.dropped += add["moe_dropped"]
        return add

    def stats(self) -> dict:
        if not self.steps:
            return {}
        return {
            "moe_load_max": self.load_max / self.steps,
            "moe_load_mean": self.load_mean / self.steps,
            "moe_local_slot_share": 100.0 * self.local / max(self.slots, 1),
            "moe_dropped": float(self.dropped),
        }


def loss_terms(fetched) -> dict:
    """For a model whose step reports a second loss term (``mtp_loss``,
    before its weight): its mean and the whole loss's over the steps one
    fetch read, as the ``fetch`` span's attributes. Empty otherwise."""
    steps = [m for m in fetched if "mtp_loss" in m]
    if not steps:
        return {}
    return {key: float(np.mean([float(m[key]) for m in steps]))
            for key in ("loss", "mtp_loss")}


def _landed(x) -> bool:
    """Whether a device array has landed (host values always have)."""
    is_ready = getattr(x, "is_ready", None)
    return True if is_ready is None else bool(is_ready())


def train_one_epoch(
    state,
    train_step: Callable,
    batches,
    *,
    epoch: int,
    num_batches: int,
    print_freq: int = 10,
    verbose: bool = True,
    feed_stats: Callable = None,
    start_step: int = 0,
    should_stop: Callable = None,
    on_step: Callable = None,
    ckpt_every: int = 0,
    ckpt_cb: Callable = None,
    emergency_cb: Callable = None,
):
    """One training epoch. ``batches`` yields device-ready batch dicts.

    Returns ``(state, stats)`` with host-float averages for the epoch.
    ``feed_stats`` (optional, e.g. ``DataLoader.feed_stats``) is called
    once at epoch end and its entries (workers_mode, cache hit rate, …)
    are merged into the stats — the input-pipeline half of the feed-rate
    telemetry, alongside the loop's own ``data_time``/``starvation``.

    Resilience hooks (all optional, dptpu/resilience):

    * ``start_step`` — batches of this epoch already consumed before a
      mid-epoch resume (display offset + step accounting; the caller
      feeds a correspondingly-skipped batch iterator);
    * ``should_stop()`` — checked after every completed step; True means
      a preemption signal arrived: stop cleanly NOW (the in-flight step
      is already finished) and return ``stats["preempted"] = True`` so
      the caller saves a mid-epoch checkpoint and exits 0;
    * ``on_step()`` — fault-injection tick, called after each step;
    * ``ckpt_cb(state, steps_done)`` — called every ``ckpt_every`` steps
      with the post-step state (the ``--ckpt-steps`` writer);
    * ``emergency_cb(state, steps_done)`` — called (best-effort, errors
      swallowed) when the loop dies on an unexpected exception, with the
      last CONSISTENT ``(state, position)`` pair, so even a crash between
      epoch boundaries loses at most the in-flight step.
    """
    batch_time = AverageMeter("Time", ":6.3f")
    data_time = AverageMeter("Data", ":6.3f")
    losses = AverageMeter("Loss", ":.4e")
    top1 = AverageMeter("Acc@1", ":6.2f")
    top5 = AverageMeter("Acc@5", ":6.2f")
    mtp_losses = AverageMeter("Mtp", ":.4e")  # a second loss term, if any
    progress = ProgressMeter(
        num_batches,
        [batch_time, data_time, losses, top1, top5],
        prefix=f"Epoch: [{epoch}]",
    )

    pending = []  # (device_metrics, n) buffered until the next display
    last_lr = 0.0
    # trust-ratio telemetry (LARS/LAMB steps only): last fetched values,
    # reported like lr — absent keys mean a plain-SGD step
    opt_last = {}
    _TRUST_KEYS = ("trust_min", "trust_mean", "trust_max")
    moe_load = MoeLoad()

    def fetch_attrs(metrics):
        """What the steps one fetch read put on its span (None: nothing)."""
        return {**moe_load.take(metrics), **loss_terms(metrics)} or None

    steps_done = start_step  # batches of THIS epoch consumed so far
    preempted = False
    # step-phase spans (dptpu/obs): data_wait / step / fetch / ckpt plus
    # a per-step "iter" envelope — the host half of the epoch
    # attribution report. A NullTracer makes every record a no-op.
    # With a real tracer the step and iter spans carry what only this
    # thread can see at this moment (a few microseconds an iteration):
    # its own CPU seconds, how many earlier steps are still in flight on
    # the device, whether the loop had to wait for the device before it
    # could dispatch, and whether the batch had landed when the step was
    # dispatched. A NullTracer asks for none of it but the one probe
    # that bounds the run-ahead.
    tracer = obs.get_tracer()
    traced = tracer.enabled
    if traced:
        tracer.reanchor()  # ts never extrapolates over more than an epoch
    pc = time.perf_counter
    tt = time.thread_time
    c_iter0 = 0.0
    end = time.time()
    it = iter(batches)
    i = -1
    try:
        while True:
            t_iter0 = pc()
            if traced:
                c_iter0 = tt()
            try:
                batch = next(it)
            except StopIteration:
                break
            i += 1
            t_data = pc()
            tracer.record("data_wait", t_iter0, t_data - t_iter0,
                          step=steps_done)
            data_time.update(time.time() - end)
            # bounded run-ahead: step i - MAX_IN_FLIGHT has to have
            # landed before step i goes out. Steps finish in order, so
            # one probe of that step's loss says it; where the device
            # sets the pace the loop waits here, one device step in
            # every iteration. The step span starts after the wait, as
            # data_wait ends before it.
            t_step, paced = t_data, False
            if len(pending) >= MAX_IN_FLIGHT:
                oldest = pending[-MAX_IN_FLIGHT][0]["loss"]
                if not _landed(oldest):
                    jax.block_until_ready(oldest)  # dptpu: allow-host-sync(bounded run-ahead: waits for step i - MAX_IN_FLIGHT alone, so the step running and the one queued behind it stay in flight and the device never idles)
                    t_step, paced = pc(), True
                    tracer.record("pace", t_data, t_step - t_data,
                                  step=steps_done)
            if traced:
                c_data = tt()
                # what the dispatch finds: count back from the newest
                # to the first whose loss has landed, which is the step
                # probed above at the latest
                inflight = 0
                for m, _ in reversed(pending):
                    if _landed(m["loss"]):
                        break
                    inflight += 1
                input_ready = _landed(
                    batch["images"] if "images" in batch
                    else batch["tokens"])
            n = int(np.prod(batch["labels"].shape))
            state, metrics = train_step(state, batch)
            if traced:
                tracer.record(
                    "step", t_step, pc() - t_step, step=steps_done,
                    attrs={"cpu_s": tt() - c_data, "inflight": inflight,
                           "paced": paced,
                           "input_ready": input_ready},
                )
            steps_done += 1
            pending.append((metrics, n))
            if i % print_freq == 0:
                # one fetch per interval, lagged by the run-ahead's depth:
                # every step older than the newest MAX_IN_FLIGHT landed
                # before this iteration's dispatch, so the fetch reads
                # them without waiting and leaves the queue as it is. The
                # first display (i == 0) fetches everything so the epoch's
                # opening line shows real values (the queue is cold there
                # anyway).
                # (capped below print_freq so short intervals still advance the
                # display every interval instead of repeating stale values;
                # such a fetch waits for the steps it reads)
                lag = 0 if i == 0 else min(MAX_IN_FLIGHT,
                                           max(print_freq - 1, 0))
                cut = max(len(pending) - lag, 0)
                ready, pending = pending[:cut], pending[cut:]
                t_fetch = pc()
                fetched = jax.device_get(  # dptpu: allow-host-sync(the ONE lagged fetch per print interval — it reads steps the bounded run-ahead has already seen land; the newest MAX_IN_FLIGHT stay in flight)
                    [(p[0], p[1]) for p in ready])
                for m, nb in fetched:
                    losses.update(float(m["loss"]), nb)
                    top1.update(float(m["top1"]), nb)
                    top5.update(float(m["top5"]), nb)
                    last_lr = float(m.get("lr", last_lr))
                    if "mtp_loss" in m:
                        mtp_losses.update(float(m["mtp_loss"]), nb)
                    for tk in _TRUST_KEYS:
                        if tk in m:
                            opt_last[tk] = float(m[tk])
                tracer.record("fetch", t_fetch, pc() - t_fetch,
                              step=steps_done - 1,
                              attrs=fetch_attrs([m for m, _ in fetched]))
                batch_time.update(time.time() - end)
                if verbose:
                    progress.display(i + start_step)
            else:
                batch_time.update(time.time() - end)
            if ckpt_every and ckpt_cb is not None \
                    and steps_done % ckpt_every == 0:
                t_ckpt = pc()
                ckpt_cb(state, steps_done)
                # steps_done already advanced: label the save with the
                # 0-based index of the step whose completion triggered
                # it, matching this iteration's data_wait/step/iter
                # spans (the anomaly report joins phases by this label)
                tracer.record("ckpt", t_ckpt, pc() - t_ckpt,
                              step=steps_done - 1)
            # the iter envelope closes BEFORE the on_step hook: a
            # profile-trigger window that ends on this tick must see
            # this step's iter span (the hook itself is microseconds)
            if traced:
                tracer.record("iter", t_iter0, pc() - t_iter0,
                              step=steps_done - 1,
                              attrs={"cpu_s": tt() - c_iter0})
            if on_step is not None:
                on_step()
            if should_stop is not None and should_stop():
                preempted = True
                break
            # re-stamp AFTER the hooks: a checkpoint save (gather +
            # device_get + fsync) must not be billed to the next step's
            # data_time / starvation feed telemetry
            end = time.time()
    except BaseException:
        if emergency_cb is not None:
            # the last fully-applied step is (state, steps_done) — a
            # consistent resume point even when the exception hit mid-step
            try:
                emergency_cb(state, steps_done)
            except Exception:
                pass
        raise
    t_fetch = pc()
    fetched = jax.device_get(pending)  # dptpu: allow-host-sync(epoch-tail drain: the last un-fetched steps sync once, after the loop)
    for m, nb in fetched:
        losses.update(float(m["loss"]), nb)
        top1.update(float(m["top1"]), nb)
        top5.update(float(m["top5"]), nb)
        last_lr = float(m.get("lr", last_lr))
        if "mtp_loss" in m:
            mtp_losses.update(float(m["mtp_loss"]), nb)
        for tk in _TRUST_KEYS:
            if tk in m:
                opt_last[tk] = float(m[tk])
    if pending:
        # the epoch-tail sync: the last un-fetched steps drain here
        tracer.record("fetch", t_fetch, pc() - t_fetch,
                      step=steps_done - 1,
                      attrs=fetch_attrs([m for m, _ in fetched]))
    stats = {
        "loss": losses.avg,
        "top1": top1.avg,
        "top5": top5.avg,
        "lr": last_lr,
        "batch_time": batch_time.avg,
        "data_time": data_time.avg,
        # fraction of epoch wall time spent WAITING on host data — the
        # feed-rate health number (≈0 when the loader keeps up; → 1 when
        # the chip starves; the reference watches the same ratio through
        # its Data meter, imagenet_ddp_apex.py:304-351)
        "starvation": data_time.sum / max(batch_time.sum, 1e-9),
        "num_batches": i + 1,
        "steps_done": steps_done,
        "preempted": preempted,
        **opt_last,
        **moe_load.stats(),
        **({"mtp_loss": mtp_losses.avg} if mtp_losses.count else {}),
    }
    if feed_stats is not None:
        for k, v in feed_stats().items():
            stats.setdefault(k, v)
    return state, stats


def validate(
    state,
    eval_step: Callable,
    batches,
    *,
    num_batches: int,
    print_freq: int = 10,
    verbose: bool = True,
    count_divisor: int = 1,
):
    """Full validation pass; returns ``{top1, top5, loss, count}`` with exact
    global aggregation (sharded val + psum — the Apex behavior,
    imagenet_ddp_apex.py:232-234,457-460 — with a single final sync).

    ``count_divisor``: in full-val-on-every-rank mode (ddp/nd,
    imagenet_ddp.py:186-194) every host feeds the full val set, so the
    psum counts each sample once per host; the averages are unaffected
    (numerator and denominator scale together) and the divisor restores
    the true sample count in the report."""
    batch_time = AverageMeter("Time", ":6.3f", Summary.NONE)
    progress = ProgressMeter(num_batches, [batch_time], prefix="Test: ")

    tracer = obs.get_tracer()
    pc = time.perf_counter
    device_sums = []
    end = time.time()
    it = iter(batches)
    i = -1
    while True:
        t0 = pc()
        try:
            batch = next(it)
        except StopIteration:
            break
        i += 1
        t_data = pc()
        tracer.record("data_wait", t0, t_data - t0, step=i)
        device_sums.append(eval_step(state, batch))
        tracer.record("eval_step", t_data, pc() - t_data, step=i)
        batch_time.update(time.time() - end)
        end = time.time()
        if verbose and i % print_freq == 0:
            progress.display(i)
    totals = {"loss_sum": 0.0, "correct1": 0.0, "correct5": 0.0, "count": 0.0}
    t_fetch = pc()
    for sums in jax.device_get(device_sums):  # dptpu: allow-host-sync(validation's single final sync — the Apex sharded-val behavior without its per-step stall)
        for k in totals:
            totals[k] += float(sums[k])
    if device_sums:
        tracer.record("fetch", t_fetch, pc() - t_fetch, step=i)
    count = max(totals["count"], 1.0)
    stats = {
        "top1": 100.0 * totals["correct1"] / count,
        "top5": 100.0 * totals["correct5"] / count,
        "loss": totals["loss_sum"] / count,
        "count": totals["count"] / count_divisor,
        "batch_time": batch_time.avg,
    }
    if verbose:
        # reference summary line (imagenet_ddp.py:321-322)
        print(" * Acc@1 {top1:.3f} Acc@5 {top5:.3f}".format(**stats))
    return stats
