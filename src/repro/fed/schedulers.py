"""Scheduler policies driving a :class:`~repro.fed.session.FedSession`.

A scheduler owns *when* training happens and *when* the session merges;
the session owns *what* a merge means (strategy, redistribution, wire
accounting). Three policies:

``SyncRound``      Cohort barrier: sample → broadcast → train all → one
                   ``aggregate_round``. Reproduces the pre-refactor
                   ``run_experiment`` loop bit-for-bit at fixed seed
                   (golden-tested).

``SemiSync``       Deadline-based straggler cutoff: the whole cohort is
                   broadcast and starts training, but only clients whose
                   simulated duration (1/speed) beats the deadline make it
                   into the round's aggregation — the stragglers' work is
                   wasted, which is exactly the semi-synchronous
                   trade-off. With ``deadline=None`` the deadline is a
                   quantile of the population's durations. An infinite
                   deadline reduces exactly to ``SyncRound``.

``BufferedAsync``  Discrete-event simulation (clients finish at 1/speed
                   intervals) with a K-buffer: updates accumulate and the
                   session merges a full buffer in ONE staleness-discounted
                   engine call (``flush_async``) instead of one call per
                   event. ``buffer_size=1`` reproduces the legacy
                   ``AsyncFedServer.submit`` event-by-event running
                   average exactly.

All schedulers share the session's redistribution path, so spectrum and
per-target rank adaptation work in every mode.

A cohort round records this span tree (``fed/session.py`` names the
tracks; each span also reaches a running profiler capture)::

    fed.round                        whole round, index as an argument
      fed.broadcast                  fed.redistribute, fed.downlink,
                                     fed.restack
      fed.data                       the cohort's batches (data_fn)
      fed.train                      the trainer's dispatch only: its
                                     device time is read from the trace
      fed.collect                    fed.uplink, fed.restack
      fed.aggregate                  merge dispatch + head FedAvg
      fed.close                      loss read-back (blocks) + history
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.fed.client import join_adapters, split_adapters
from repro.fed.session import AsyncConfig, ROUNDS_TRACK, SERVER_TRACK


class Scheduler:
    name = "base"


def _wire_rows(history, session) -> None:
    """The round's measured downlink and uplink bytes."""
    history["downlink_bytes"].append(session.comm_log["downlink"][-1])
    history["uplink_bytes"].append(session.comm_log["uplink"][-1])


def _eval_round(history, session, eval_fn, do_eval: bool) -> None:
    if eval_fn is None:
        return
    if do_eval or not history["eval_acc"]:
        m = eval_fn(session.global_lora, session.global_head)
        history["eval_acc"].append(float(m["acc"]))
        history["eval_loss"].append(float(m["loss"]))
    else:
        history["eval_acc"].append(history["eval_acc"][-1])
        history["eval_loss"].append(history["eval_loss"][-1])


@dataclass
class SyncRound(Scheduler):
    """Synchronous cohort rounds (the paper's mode).

    ``topology`` (a :class:`~repro.fed.topology.HierarchicalTopology`)
    replaces the flat collect+aggregate with the two-tier edge/root path;
    ``None`` is the original flat round, bit-for-bit (golden-tested)."""
    name = "sync"

    topology: Optional[object] = None

    def run(self, session, train, data_fn, num_rounds: int,
            eval_fn=None, eval_every: int = 1) -> Dict[str, List]:
        """``train(frozen, trainable, masks, data) -> (trainable, losses)``
        is the vmapped cohort trainer (a third output, a model's routing
        statistics, goes to ``session.record_routing``);
        ``data_fn(cohort, rnd)`` returns
        the cohort's stacked batches. Resuming a restored session
        continues the round index from ``session.rounds_done``."""
        history: Dict[str, List] = {
            "round": [], "train_loss": [], "eval_acc": [], "eval_loss": [],
            "downlink_bytes": [], "uplink_bytes": []}
        rec = session.rec
        for i in range(num_rounds):
            rnd = session.rounds_done
            with rec.span("fed.round", ROUNDS_TRACK, round=rnd) as whole:
                cohort = session.sample_cohort()
                stacked, heads = session.broadcast_cohort(cohort)
                factors, masks = split_adapters(stacked)
                trainable = {"factors": factors, "head": heads}
                with rec.span("fed.data", SERVER_TRACK, round=rnd):
                    batches = data_fn(cohort, rnd)
                with rec.span("fed.train", SERVER_TRACK, round=rnd,
                              cohort=len(cohort)):
                    out = train(session.base, trainable, masks, batches)
                trainable, losses = out[:2]
                trained = join_adapters(trainable["factors"], masks)
                if self.topology is not None:
                    self.topology.aggregate(session, cohort, trained,
                                            trainable["head"])
                else:
                    tree, up_heads = session.collect_updates(
                        cohort, trained, trainable["head"])
                    session.aggregate_round(tree, cohort,
                                            stacked_heads=up_heads)
                with rec.span("fed.close", SERVER_TRACK, round=rnd):
                    history["round"].append(rnd)
                    history["train_loss"].append(float(jnp.mean(losses)))
                    if len(out) > 2:
                        session.record_routing(out[2])
                    _wire_rows(history, session)
            if rec.enabled:
                session.metrics.histogram("fed.round_s").observe(
                    whole.seconds)
            _eval_round(history, session, eval_fn,
                        rnd % eval_every == 0 or i == num_rounds - 1)
        return history


@dataclass
class SemiSync(Scheduler):
    """Deadline-cutoff semi-synchronous rounds (straggler mitigation)."""
    name = "semisync"

    speeds: np.ndarray = None          # per-client relative speed
    deadline: Optional[float] = None   # None -> quantile of 1/speeds
    deadline_quantile: float = 0.75

    def resolved_deadline(self) -> float:
        if self.deadline is not None:
            return float(self.deadline)
        return float(np.quantile(1.0 / np.asarray(self.speeds, np.float64),
                                 self.deadline_quantile))

    def run(self, session, train, data_fn, num_rounds: int,
            eval_fn=None, eval_every: int = 1) -> Dict[str, List]:
        speeds = np.asarray(self.speeds, np.float64)
        deadline = self.resolved_deadline()
        history: Dict[str, List] = {
            "round": [], "train_loss": [], "eval_acc": [], "eval_loss": [],
            "downlink_bytes": [], "uplink_bytes": [], "stragglers": [],
            "round_time": []}
        rec = session.rec
        for i in range(num_rounds):
            rnd = session.rounds_done
            with rec.span("fed.round", ROUNDS_TRACK, round=rnd) as whole:
                cohort = session.sample_cohort()
                durations = 1.0 / speeds[cohort]
                keep = durations <= deadline
                if not keep.any():                 # never stall a round
                    keep[np.argmin(durations)] = True
                cut = int((~keep).sum())
                if rec.enabled and cut:
                    rec.instant("deadline_cut", ROUNDS_TRACK, round=rnd,
                                stragglers=cut, deadline=deadline)
                stacked, heads = session.broadcast_cohort(cohort)
                factors, masks = split_adapters(stacked)
                trainable = {"factors": factors, "head": heads}
                with rec.span("fed.data", SERVER_TRACK, round=rnd):
                    batches = data_fn(cohort, rnd)
                with rec.span("fed.train", SERVER_TRACK, round=rnd,
                              cohort=len(cohort)):
                    out = train(session.base, trainable, masks, batches)
                trainable, losses = out[:2]
                trained = join_adapters(trainable["factors"], masks)
                idx = np.flatnonzero(keep)
                sub_tree = {t: {leaf: ad[leaf][idx]
                                for leaf in ("A", "B", "mask")}
                            for t, ad in trained.items()}
                sub_heads = None if not trainable["head"] else {
                    k: v[idx] for k, v in trainable["head"].items()}
                tree, up_heads = session.collect_updates(
                    cohort[idx], sub_tree, sub_heads)
                session.aggregate_round(tree, cohort[idx],
                                        stacked_heads=up_heads)
                with rec.span("fed.close", SERVER_TRACK, round=rnd):
                    history["round"].append(rnd)
                    history["train_loss"].append(
                        float(jnp.mean(jnp.asarray(losses)[idx])))
                    if len(out) > 2:
                        session.record_routing(out[2])
                    _wire_rows(history, session)
                    history["stragglers"].append(cut)
                    session.metrics.counter("fed.stragglers").inc(cut)
                    # the server closes the round when every survivor is
                    # in: at durations.max() if nobody was cut, else at
                    # the deadline — unless the force-kept fastest itself
                    # finishes after it
                    round_time = (
                        float(durations.max()) if keep.all()
                        else float(max(deadline, durations[keep].max())))
                    history["round_time"].append(round_time)
                    # simulated time, no clock read: always on
                    session.metrics.histogram("fed.round_time_sim").observe(
                        round_time)
            if rec.enabled:
                session.metrics.histogram("fed.round_s").observe(
                    whole.seconds)
            _eval_round(history, session, eval_fn,
                        rnd % eval_every == 0 or i == num_rounds - 1)
        return history


@dataclass
class BufferedAsync(Scheduler):
    """K-buffered staleness-discounted asynchronous merging.

    ``acfg=None`` (default) uses the session's own staleness policy; an
    explicit AsyncConfig here overrides it for the run.

    The live event heap / pending adapters / K-buffer are installed on
    ``session.async_state`` and mutated in place, so ``session.save()``
    can checkpoint a run *mid-flight* and a restored session resumes the
    event sequence exactly (heap order, staleness, buffer contents —
    bit-identical, tested). A fresh run cold-starts only when the session
    carries no async state. ``drain=False`` leaves a partial buffer
    unflushed at the end of ``run`` — the setting that makes a split run
    (run → save → restore → run) equal one uninterrupted run."""
    name = "buffered_async"

    speeds: np.ndarray = None
    buffer_size: int = 1
    acfg: Optional[AsyncConfig] = None
    drain: bool = True

    def run(self, session, local_train, data_fn, num_events: int,
            eval_fn=None, eval_every: Optional[int] = None
            ) -> Dict[str, List]:
        """Discrete-event loop: each client trains for 1/speed time units;
        completions are processed in arrival order. ``local_train`` is the
        single-client trainer; ``data_fn(cid)`` returns one client's
        batches. ``eval_every`` (events) adds eval_acc/eval_loss rows.
        An explicit scheduler ``acfg`` applies only inside this run; the
        session's own staleness policy is restored afterwards."""
        prev_acfg = session.acfg
        if self.acfg is not None:
            session.acfg = self.acfg
        try:
            return self._run(session, local_train, data_fn, num_events,
                             eval_fn, eval_every)
        finally:
            session.acfg = prev_acfg

    def _run(self, session, local_train, data_fn, num_events,
             eval_fn, eval_every) -> Dict[str, List]:
        speeds = np.asarray(self.speeds, np.float64)
        n = session.scfg.num_clients
        if session.async_state is None:
            heap: List[Tuple[float, int, int]] = []  # (finish, cid, ver)
            pending: Dict[int, Dict] = {}
            buffer: List = []
            for cid in range(n):
                ad, ver = session.adapter_for(cid)
                pending[cid] = ad
                heapq.heappush(heap, (1.0 / speeds[cid], cid, ver))
            session.async_state = {"heap": heap, "pending": pending,
                                   "buffer": buffer}
        else:
            # resume mid-flight (restored checkpoint or a previous run's
            # live state): the heap list is already heap-ordered
            st = session.async_state
            heap, pending, buffer = st["heap"], st["pending"], st["buffer"]
        history: Dict[str, List] = {
            "time": [], "staleness": [], "accepted": [], "flush_events": [],
            "downlink_bytes": [], "uplink_bytes": [],
            "eval_acc": [], "eval_loss": []}
        comm_seen = {k: sum(v) for k, v in session.comm_log.items()}

        def flush():
            if not buffer:
                return
            flags = session.flush_async(buffer)
            history["staleness"].extend(
                session.staleness_log[-len(buffer):])
            history["accepted"].extend(flags)
            history["flush_events"].append(len(buffer))
            buffer.clear()

        rec = session.rec
        for step in range(num_events):
            t_now, cid, ver = heapq.heappop(heap)
            factors, masks = split_adapters(pending[cid])
            trainable = {"factors": factors, "head": session.global_head}
            # one track per client: training bursts and arrivals line up
            # against the server's flush spans
            track = f"fed.client{cid}"
            with rec.span("fed.data", track):
                batches = data_fn(cid)
            with rec.span("fed.train", track, version=int(ver),
                          t_sim=float(t_now)):
                out = local_train(session.base, trainable, masks, batches)
            trained = out[0]
            if len(out) > 2:
                session.record_routing(out[2])
            if rec.enabled:
                rec.instant("update_arrival", track, version=int(ver),
                            staleness=int(session.version - ver))
            with rec.span("fed.collect", SERVER_TRACK, cohort=1):
                buffer.append(session.make_update(
                    cid, join_adapters(trained["factors"], masks), ver,
                    head=trained["head"]))
            if len(buffer) >= self.buffer_size:
                flush()
            history["time"].append(t_now)
            if eval_fn is not None and eval_every and \
                    (step % eval_every == 0 or step == num_events - 1):
                m = eval_fn(session.global_lora, session.global_head)
                history["eval_acc"].append(float(m["acc"]))
                history["eval_loss"].append(float(m["loss"]))
            ad, ver = session.adapter_for(cid)
            pending[cid] = ad
            heapq.heappush(heap, (t_now + 1.0 / speeds[cid], cid, ver))
            # measured wire bytes this event (uplink update + fresh
            # re-broadcast; the pre-loop cold broadcasts to all clients
            # are excluded here but counted in session.comm_totals())
            for key, col in (("downlink", "downlink_bytes"),
                             ("uplink", "uplink_bytes")):
                tot = sum(session.comm_log[key])
                history[col].append(tot - comm_seen[key])
                comm_seen[key] = tot
        if self.drain:
            flush()                              # drain a partial buffer
        return history
