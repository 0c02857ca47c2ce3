"""FedSession — the one transport-agnostic front door of the fed layer.

Before this module the fed layer had three divergent entry points
(``FedServer``, ``AsyncFedServer``, ``run_experiment``) that duplicated
redistribution/rank logic and disagreed on it: the async path applied the
hlora r/r_max scale correction even for the naive baseline, supported
neither spectrum nor per-target rank adaptation, and EMA'd the task head
out-of-band. ``FedSession`` unifies all of it:

* **State**: frozen base, global adapter at r_max, task head, per-client
  ranks, per-target rank caps, rng, version/round counters, comm log.
* **Strategy** (``fed/strategies.py``): a pluggable object naming the
  batched-engine aggregation config and the redistribution scale policy.
  Sync rounds and async flushes drive the *same* engine with the *same*
  strategy — no string dispatch, no divergent math.
* **Shared redistribution**: ``redistribute`` masks the global to each
  client's rank (clamped by per-target caps from spectrum adaptation) and
  applies the strategy's scale correction. The sync broadcast, the async
  ``adapter_for``, and every scheduler all call this one path.
* **Wire accounting** (``fed/messages.py``): ``broadcast_cohort`` /
  ``collect_updates`` / ``make_update`` round-trip payloads through real
  serialized ``Broadcast``/``ClientUpdate`` messages — rank-truncated and
  dtype-aware — and log measured uplink/downlink bytes. Round-trip is
  bit-exact (masked directions are exactly zero), so the measured path IS
  the compute path. The cohort path serializes each client from one host
  copy of the stack a round and sends the decoded stack back in one
  transfer (``fed.wire_transfers`` counts them): per-client device slices
  and read-backs cost a dispatch or a device sync each, which left the
  chip idle for most of a round.
* **Schedulers** (``fed/schedulers.py``): ``SyncRound`` / ``SemiSync`` /
  ``BufferedAsync`` drive the session; the session itself never blocks on
  a cohort barrier — ``aggregate_round`` and ``flush_async`` are the only
  merge entry points.
* **Checkpoint/resume** (``save`` / ``restore``): global factors + masks +
  ranks + rng/scheduler counters through ``checkpoint/store.py``; a
  restored session continues a sync run bit-identically.

``FedServer`` / ``AsyncFedServer`` remain as deprecated shims subclassing
this session (fed/server.py, fed/async_server.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import agg_engine
from repro.core import rank as rank_lib
from repro.fed import compress as compress_lib
from repro.fed import messages as msg_lib
from repro.fed import strategies as strat_lib
from repro.fed.population import sampler_from_name
from repro.models import transformer as tf_lib
from repro.obs import NULL_RECORDER, MetricsRegistry

#: ring-buffer tracks of the fed spans: whole rounds, the server's
#: phases within a round, and the parts of a phase (a track per level,
#: so no track ever nests spans)
ROUNDS_TRACK = "fed.rounds"
SERVER_TRACK = "fed.server"
PARTS_TRACK = "fed.server.parts"


@dataclass
class ServerConfig:
    num_clients: int = 100
    clients_per_round: int = 20
    strategy: str = "hlora"          # naive | hlora | flora
    svd_method: str = "factored"     # factored | exact | randomized
    split: str = "paper"             # paper | sqrt
    # uniform | random | capacity | data | spectrum
    # 'spectrum' (beyond-paper) answers the paper's open question: after
    # each aggregation the server reads the singular spectrum of ΔW' (free
    # — it just ran the SVD) and assigns the smallest rank capturing
    # ``spectrum_energy`` of it, clamped per-client by capacity.
    rank_policy: str = "random"
    spectrum_energy: float = 0.95
    # Per-*target* refinement of the spectrum policy: each LoRA target
    # (q, v, w1, ...) gets its own energy rank from its own spectrum —
    # attention projections routinely concentrate in fewer directions
    # than MLP ones, and one pooled rank overpays the tight targets.
    # Redistribution then masks target t to min(r_client, r_target).
    per_target_ranks: bool = False
    r_min: int = 2
    r_max: int = 8
    seed: int = 0
    # Wire codec for every Broadcast/ClientUpdate ("none" keeps the
    # message path byte-identical to the raw format): none | bf16 |
    # int8 | topk[:k]  (fed/compress.py)
    codec: str = "none"


@dataclass
class AsyncConfig:
    """Staleness policy for async merges (FedAsync-style)."""
    staleness_exp: float = 0.5     # polynomial discount (1+τ)^-exp
    base_weight: float = 0.25      # mixing rate for fresh updates
    max_staleness: int = 16        # drop updates older than this


def assign_ranks(scfg: ServerConfig, client_sizes, capacities=None,
                 rng=None) -> np.ndarray:
    n = scfg.num_clients
    if scfg.rank_policy == "uniform":
        return rank_lib.uniform_ranks(n, scfg.r_max)
    if scfg.rank_policy == "random":
        return rank_lib.random_ranks(n, scfg.r_min, scfg.r_max, scfg.seed)
    if scfg.rank_policy == "capacity":
        caps = capacities if capacities is not None else \
            (rng or np.random.default_rng(scfg.seed)).random(n)
        return rank_lib.capacity_ranks(caps, scfg.r_min, scfg.r_max)
    if scfg.rank_policy == "data":
        return rank_lib.data_ranks(client_sizes, scfg.r_min, scfg.r_max)
    if scfg.rank_policy == "spectrum":
        # starts at r_max; adapt_ranks() tightens it after each round
        return rank_lib.uniform_ranks(n, scfg.r_max)
    raise ValueError(scfg.rank_policy)


def client_slice(tree, heads, i: int):
    """Client ``i``'s slice of a cohort stack and of its heads (None
    stays None)."""
    sl = {t: {leaf: v[i] for leaf, v in ad.items()}
          for t, ad in tree.items()}
    h = None if heads is None else {n: v[i] for n, v in heads.items()}
    return sl, h


class FedSession:
    def __init__(self, cfg: ModelConfig, scfg: ServerConfig, base_params,
                 client_sizes: Optional[Sequence[int]] = None,
                 capacities: Optional[Sequence[float]] = None,
                 engine: Optional[agg_engine.AggregationEngine] = None,
                 strategy=None,
                 acfg: Optional[AsyncConfig] = None,
                 track_comm: bool = True,
                 mesh=None,
                 recorder=None,
                 metrics: Optional[MetricsRegistry] = None,
                 population=None,
                 sampler=None,
                 codec=None):
        from repro.fed.client import split_head
        self.cfg = cfg
        self.scfg = scfg
        self.acfg = acfg if acfg is not None else AsyncConfig()
        if strategy is None:
            strategy = scfg.strategy
        self.strategy = (strategy if isinstance(
            strategy, strat_lib.AggregationStrategy)
            else strat_lib.from_name(strategy, scfg))
        frozen, head = split_head(base_params)
        self.base = frozen
        self.global_head = head   # task head: FedAvg'd in-session
        self.rng = np.random.default_rng(scfg.seed)
        # Population-scale mode (fed/population.py): client metadata
        # (sizes/ranks) comes from the lazily-materialized population,
        # shard data is built per round by the data_fn — the session
        # itself only ever holds the sampled cohort's updates.
        self.population = population
        self.sampler = sampler_from_name(sampler)
        if population is not None:
            if population.size != scfg.num_clients:
                raise ValueError(
                    f"population has {population.size} clients but "
                    f"scfg.num_clients={scfg.num_clients}")
            if client_sizes is None:
                client_sizes = population.num_examples
        elif self.sampler is not None:
            raise ValueError("a sampler needs a population")
        self.client_sizes = np.asarray(
            client_sizes if client_sizes is not None
            else np.full(scfg.num_clients, 64), np.int64)
        self.ranks = assign_ranks(scfg, self.client_sizes, capacities,
                                  self.rng)
        if population is not None and population.ranks is not None:
            self.ranks = population.ranks.astype(np.int32).copy()
        # Wire codec applied to every Broadcast/ClientUpdate; None keeps
        # the message bytes identical to the raw format (golden-safe).
        self.codec = compress_lib.from_name(
            codec if codec is not None else getattr(scfg, "codec", "none"))
        # Global adapter at full rank (A gaussian, B zero => ΔW = 0).
        self.global_lora = tf_lib.init_lora(jax.random.PRNGKey(scfg.seed),
                                            cfg)
        # Batched aggregation engine: one compiled call per merge, cached
        # on tree structure. Shared process-wide by default so every
        # session (and the benchmarks) reuse one jit cache. Passing a
        # ``mesh`` makes every strategy × scheduler multi-device through
        # this one choke point: the engine shard_maps each stacked
        # aggregation batch over the mesh's data axes.
        if engine is not None:
            self.engine = engine
        elif mesh is not None:
            self.engine = agg_engine.AggregationEngine(mesh=mesh)
        else:
            self.engine = agg_engine.default_engine()
        # Singular spectrum of the last aggregated ΔW' per target,
        # {target: (*stack, r_max)} — surfaced by the engine for free.
        self.last_spectrum: Optional[dict] = None
        # Per-target rank caps ({target: r}) set by adapt_ranks when
        # scfg.per_target_ranks; None until the first adaptation.
        self.target_ranks: Optional[Dict[str, int]] = None
        self.rounds_done = 0
        self.version = 0                      # async merge counter
        self.staleness_log: List[int] = []
        self.track_comm = track_comm
        # Measured wire bytes, one entry per broadcast_cohort /
        # collect_updates / make_update / adapter_for call.
        self.comm_log: Dict[str, List[int]] = {"downlink": [], "uplink": []}
        # Observability: recorder defaults to the no-op singleton (its
        # spans still reach a running profiler capture); metrics are
        # always on. Server phases record on SERVER_TRACK, their parts
        # on PARTS_TRACK.
        self.rec = recorder if recorder is not None else NULL_RECORDER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        if population is not None and population.metrics is None:
            population.metrics = self.metrics
        # Live BufferedAsync scheduler state ({heap, pending, buffer}),
        # installed by the scheduler and serialized by save/restore so a
        # long async run can checkpoint mid-flight (exactly).
        self.async_state: Optional[dict] = None

    def _log_comm(self, direction: str, nbytes: int,
                  track: str = "fed.wire") -> None:
        """The one comm accounting choke point: the historical per-call
        ``comm_log`` rows, a registry byte counter, and (recording on) a
        wire-traffic counter sample on the shared timeline. New
        directions (e.g. the topology's per-edge ``edge<i>_uplink``)
        create their own log column and counter; ``track`` routes their
        timeline samples onto per-edge tracks."""
        self.comm_log.setdefault(direction, []).append(nbytes)
        self.metrics.counter(f"fed.{direction}_bytes").inc(int(nbytes))
        if self.rec.enabled:
            self.rec.counter_sample(f"fed.{direction}_bytes", track,
                                    int(nbytes))

    # -- cohort handling ----------------------------------------------------

    def sample_cohort(self) -> np.ndarray:
        """Pick this round's cohort. With a sampler (population mode) the
        pluggable policy draws from the session rng — same seeded stream,
        so runs stay bit-reproducible; the default is the original
        uniform draw, untouched (golden-tested)."""
        if self.sampler is not None:
            cohort = np.asarray(self.sampler.sample(
                self.population, self.rng, self.rounds_done,
                self.scfg.clients_per_round), np.int64)
            if self.rec.enabled:
                self.rec.instant("cohort_sampled", "fed.server",
                                 sampler=self.sampler.name,
                                 cohort=len(cohort),
                                 round=self.rounds_done)
            return cohort
        return self.rng.choice(self.scfg.num_clients,
                               size=self.scfg.clients_per_round,
                               replace=False)

    def cohort_weights(self, cohort: np.ndarray) -> jnp.ndarray:
        n_k = self.client_sizes[cohort].astype(np.float64)
        return jnp.asarray(n_k / n_k.sum(), jnp.float32)

    def cohort_heads(self, cohort: np.ndarray):
        k = len(cohort)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (k, *x.shape)),
            self.global_head)

    # -- shared redistribution path -----------------------------------------

    def _cohort_masks(self, cohort: np.ndarray, mask_shape,
                      cap: Optional[int] = None) -> jnp.ndarray:
        """Rank masks for the cohort; ``cap`` (per-target rank) clamps
        every client's rank from above — SVD components are ordered, so
        the first min(r_k, cap) directions are the optimal truncation."""
        r_max = self.cfg.lora.r_max
        k = len(cohort)
        masks = np.zeros((k, *mask_shape), np.float32)
        for i, cid in enumerate(cohort):
            r_k = int(self.ranks[cid]) if cap is None \
                else min(int(self.ranks[cid]), int(cap))
            masks[i, ...] = (np.arange(r_max) < r_k).astype(np.float32)
        return jnp.asarray(masks)

    def redistribute(self, cohort: np.ndarray) -> Dict[str, dict]:
        """THE redistribution path (sync broadcast AND async adapter_for):
        per-client rank-r_k truncation of the global adapter, clamped per
        target when per-target ranks are adapted, with the strategy's
        scale correction (hlora: r_eff/r_max on B, so the client's
        *effective* update is exactly the rank-r_k truncation of ΔW';
        naive/flora distribute plain truncated factors, as in Cho)."""
        k = len(cohort)
        r_max = self.cfg.lora.r_max
        out = {}
        with self.rec.span("fed.redistribute", PARTS_TRACK, cohort=k):
            for t, ad in self.global_lora.items():
                cap = None if self.target_ranks is None \
                    else self.target_ranks.get(t)
                m = self._cohort_masks(cohort, ad["mask"].shape, cap)
                a = jnp.broadcast_to(ad["A"][None], (k, *ad["A"].shape)) \
                    * m[..., None, :]
                b = jnp.broadcast_to(ad["B"][None], (k, *ad["B"].shape)) \
                    * m[..., :, None]
                if self.strategy.scale_correction:
                    r_eff = jnp.maximum(jnp.sum(m, axis=-1), 1.0)
                    b = b * (r_eff / float(r_max))[..., None, None]
                out[t] = {"A": a, "B": b, "mask": m}
        return out

    def _client_ranks(self, cid: int) -> Dict[str, int]:
        """Per-target effective rank for one client (cap-clamped)."""
        r = int(self.ranks[cid])
        out = {}
        for t in self.global_lora:
            cap = None if self.target_ranks is None \
                else self.target_ranks.get(t)
            out[t] = r if cap is None else min(r, int(cap))
        return out

    # -- wire-level broadcast / collect -------------------------------------

    def to_host(self, tree):
        """One device -> host transfer of a whole pytree, counted in
        ``fed.wire_transfers``."""
        self.metrics.counter("fed.wire_transfers").inc()
        return jax.device_get(tree)

    def to_device(self, tree):
        """One host -> device transfer of a whole pytree, counted in
        ``fed.wire_transfers``."""
        self.metrics.counter("fed.wire_transfers").inc()
        return jax.device_put(tree)

    def make_broadcast(self, cid: int, stacked_slice,
                       head=None) -> msg_lib.Broadcast:
        """One client's ``Broadcast`` message from its slice of the
        redistributed stack (already masked + scale-corrected); ``head``
        is a host copy of the global head (read from it when None)."""
        ranks = self._client_ranks(cid)
        payload = msg_lib.truncate_adapter(stacked_slice, ranks)
        head = self.global_head if head is None else head
        return msg_lib.Broadcast(version=self.version, client_id=int(cid),
                                 adapter=payload,
                                 head={k: np.asarray(v) for k, v
                                       in head.items()},
                                 codec=self.codec)

    def broadcast_cohort(self, cohort: np.ndarray):
        """Redistribute to a cohort through the wire format.

        Returns ``(stacked_tree, stacked_heads)`` reconstructed from the
        serialized ``Broadcast`` messages (bit-identical to the in-memory
        redistribution — masked directions are exactly zero), logging the
        measured downlink bytes. The cohort crosses between device and
        host twice a round whatever its size: one host copy of the stack
        and head, each client's message built from views of it, and one
        device copy of the decoded stack.
        """
        rec, k = self.rec, len(cohort)
        with rec.span("fed.broadcast", SERVER_TRACK, cohort=k):
            stacked = self.redistribute(cohort)
            if not self.track_comm:
                self._log_comm("downlink", 0)
                return stacked, self.cohort_heads(cohort)
            stack = msg_lib.CohortStack(k, self.cfg.lora.r_max)
            total = 0
            with rec.span("fed.downlink", PARTS_TRACK, cohort=k):
                host, head = self.to_host((
                    {t: {"A": ad["A"], "B": ad["B"]}
                     for t, ad in stacked.items()}, self.global_head))
                for i, cid in enumerate(cohort):
                    sl, _ = client_slice(host, None, i)
                    wire = msg_lib.Broadcast.from_bytes(
                        self.make_broadcast(cid, sl, head).to_bytes())
                    total += wire.num_bytes
                    stack.put(i, wire.adapter, wire.head)
            self._log_comm("downlink", total)
            with rec.span("fed.restack", PARTS_TRACK, cohort=k):
                return self.to_device((stack.tree, stack.heads))

    def adapter_for(self, cid: int) -> Tuple[Dict, int]:
        """Async client-facing broadcast: rank-r_k truncation of the
        current global adapter (shared redistribution path — strategy
        gating and per-target caps included) + server version."""
        with self.rec.span("fed.broadcast", SERVER_TRACK, cohort=1):
            stacked = self.redistribute(np.array([cid]))
            sl = {t: {k2: v[0] for k2, v in ad.items()}
                  for t, ad in stacked.items()}
            if not self.track_comm:
                return sl, self.version
            with self.rec.span("fed.downlink", PARTS_TRACK, cohort=1):
                wire = msg_lib.Broadcast.from_bytes(
                    self.make_broadcast(cid, sl).to_bytes())
                tree, _head = wire.unpack(self.cfg.lora.r_max)
            self._log_comm("downlink", wire.num_bytes)
            return tree, self.version

    def make_update(self, cid: int, trained_lora: Dict, start_version: int,
                    head=None, log: bool = True) -> msg_lib.ClientUpdate:
        """Serialize one client's trained adapter (+head) into a
        ``ClientUpdate``, logging measured uplink bytes (``log=False``
        when the caller consolidates accounting itself)."""
        ranks = {}
        for t, ad in trained_lora.items():
            m = np.asarray(ad["mask"]).reshape(-1, ad["mask"].shape[-1])
            ranks[t] = int(m[0].sum())
        upd = msg_lib.ClientUpdate(
            client_id=int(cid), start_version=int(start_version),
            num_examples=int(self.client_sizes[int(cid)]),
            adapter=msg_lib.truncate_adapter(trained_lora, ranks),
            head={k: np.asarray(v) for k, v in (head or {}).items()},
            codec=self.codec)
        # num_bytes serializes lazily — only measure when tracking, so
        # track_comm=False skips the buffer build here too
        if log:
            self._log_comm("uplink", upd.num_bytes
                           if self.track_comm else 0)
        return upd

    def collect_updates(self, cohort: np.ndarray, trained_tree: Dict,
                        trained_heads=None):
        """Round-trip a trained cohort stack through ``ClientUpdate``
        messages (measured uplink, one consolidated comm_log row per
        round), returning the re-stacked tree+heads ready for
        :meth:`aggregate_round`. Bit-exact: gradients cannot flow into
        masked directions, so truncation loses nothing. Like the
        broadcast, one host copy of the trained stack (each client's rank
        read from its mask there) and one device copy of the decoded one."""
        rec, k = self.rec, len(cohort)
        with rec.span("fed.collect", SERVER_TRACK, cohort=k):
            if not self.track_comm:
                self._log_comm("uplink", 0)
                return trained_tree, trained_heads
            stack = msg_lib.CohortStack(k, self.cfg.lora.r_max)
            total = 0
            with rec.span("fed.uplink", PARTS_TRACK, cohort=k):
                host, host_heads = self.to_host((trained_tree,
                                                 trained_heads))
                for i, cid in enumerate(cohort):
                    sl, h = client_slice(host, host_heads, i)
                    upd = msg_lib.ClientUpdate.from_bytes(
                        self.make_update(cid, sl, self.version, h,
                                         log=False).to_bytes())
                    total += upd.num_bytes
                    stack.put(i, upd.adapter, upd.head)
            self._log_comm("uplink", total)
            with rec.span("fed.restack", PARTS_TRACK, cohort=k):
                out, heads_st = self.to_device((stack.tree, stack.heads))
            return out, (heads_st or None) if trained_heads is not None \
                else None

    # -- aggregation ---------------------------------------------------------

    def aggregate_round(self, stacked_trained, cohort: np.ndarray,
                        stacked_heads=None, weights=None) -> None:
        """Synchronous cohort merge: one engine call (Eq. 2 + 3 under
        hlora/flora, Eq. 1 under naive), output at full rank r_max;
        redistribution happens lazily in ``redistribute``. Task heads are
        FedAvg'd with the same cohort weights under every strategy, so the
        comparison isolates the adapter aggregation. ``weights`` overrides
        the per-client data weights when the stacked items are not the
        cohort itself — the hierarchical root merge passes per-edge
        weights ``n_e/Σn_e`` over pre-merged edge aggregates."""
        with self.rec.span("fed.aggregate", SERVER_TRACK,
                           cohort=len(cohort), round=self.rounds_done):
            eta = self.cohort_weights(cohort) if weights is None \
                else jnp.asarray(weights, jnp.float32)
            if stacked_heads:
                self.global_head = jax.tree.map(
                    lambda x: jnp.tensordot(eta, x.astype(jnp.float32),
                                            axes=1).astype(x.dtype),
                    stacked_heads)
            full = {t: jnp.ones_like(ad["mask"][:1])
                    for t, ad in stacked_trained.items()}
            out, spectra = self.engine(
                stacked_trained, eta, self.cfg.lora.alpha,
                **self.strategy.engine_kwargs(), new_masks=full,
                key=jax.random.PRNGKey(int(self.rng.integers(2 ** 31))))
            self.global_lora = {
                t: {"A": ad["A"][0], "B": ad["B"][0], "mask": ad["mask"][0]}
                for t, ad in out.items()}
            self.last_spectrum = spectra if self.strategy.has_spectrum \
                else None
            if self.scfg.rank_policy == "spectrum":
                self.adapt_ranks()
            self.rounds_done += 1
            self.metrics.counter("fed.rounds").inc()

    def flush_async(self, updates: Sequence) -> List[bool]:
        """Buffered asynchronous merge: fold K client updates into the
        global in ONE engine call (vs one call per event in the legacy
        ``AsyncFedServer.submit``).

        Each update u_i gets weight
            w_i = base_weight · (1+τ_i)^(-staleness_exp) · n_i / n̄
        (τ_i = version − start_version_i at flush time, n̄ the buffer's
        mean data size) and the global keeps ``max(1 − Σw, 0)``; the
        engine normalizes. K=1 reduces exactly to the legacy running
        average (1−w)·G + w·U. base_weight=1 with zero staleness
        degenerates to the plain sync FedAvg of the buffer — which is
        what makes the zero-staleness equivalence testable. The task head
        is averaged with the SAME weights (fixing the out-of-band 0.9/0.1
        EMA the legacy simulation applied regardless of staleness).

        ``updates``: objects with .adapter (full-rank masked tree),
        .head (dict or empty), .start_version, .num_examples — i.e.
        unpacked ``ClientUpdate``s or ``make_update`` results.
        """
        taus = [self.version - int(u.start_version) for u in updates]
        self.staleness_log.extend(taus)
        stale_h = self.metrics.histogram("fed.staleness")
        for tau in taus:
            stale_h.observe(tau)
        keep = [i for i, tau in enumerate(taus)
                if tau <= self.acfg.max_staleness]
        flags = [i in keep for i in range(len(updates))]
        self.metrics.counter("fed.updates_merged").inc(len(keep))
        self.metrics.counter("fed.updates_dropped").inc(
            len(taus) - len(keep))
        if not keep:
            return flags
        with self.rec.span("fed.flush", SERVER_TRACK, merged=len(keep),
                           version=self.version):
            return self._flush_merge(updates, taus, keep, flags)

    def _flush_merge(self, updates, taus, keep, flags) -> List[bool]:
        survivors = [updates[i] for i in keep]
        n = np.asarray([max(int(u.num_examples), 1) for u in survivors],
                       np.float64)
        ws = [float(self.acfg.base_weight
                    * (1.0 + taus[i]) ** (-self.acfg.staleness_exp)
                    * (n[j] / n.mean()))
              for j, i in enumerate(keep)]
        residual = max(1.0 - sum(ws), 0.0)
        eta = jnp.asarray([residual] + ws, jnp.float32)
        adapters = [self._unpack_update_adapter(u) for u in survivors]
        tree = {
            t: {leaf: jnp.stack([g[leaf]] + [ad[t][leaf]
                                             for ad in adapters])
                for leaf in ("A", "B", "mask")}
            for t, g in self.global_lora.items()}
        new_masks = {t: jnp.ones_like(st["mask"][:1])
                     for t, st in tree.items()}
        out, spectra = self.engine(tree, eta, self.cfg.lora.alpha,
                                   **self.strategy.engine_kwargs(),
                                   new_masks=new_masks)
        self.global_lora = {t: {k: v[0] for k, v in ad.items()}
                            for t, ad in out.items()}
        heads = [u.head for u in survivors]
        if self.global_head and heads and all(h for h in heads):
            etan = eta / jnp.sum(eta)
            self.global_head = jax.tree.map(
                lambda g, *hs: jnp.tensordot(
                    etan, jnp.stack([g.astype(jnp.float32)]
                                    + [jnp.asarray(h, jnp.float32)
                                       for h in hs]), axes=1
                ).astype(g.dtype),
                self.global_head,
                *[{k: jnp.asarray(h[k]) for k in self.global_head}
                  for h in heads])
        self.last_spectrum = spectra if self.strategy.has_spectrum else None
        self.version += len(keep)
        if self.scfg.rank_policy == "spectrum":
            self.adapt_ranks()
        return flags

    def _unpack_update_adapter(self, u) -> Dict:
        """An update's adapter either arrives full-rank with masks (direct
        submit) or rank-truncated from the wire (ClientUpdate)."""
        ad = u.adapter
        first = next(iter(ad.values()))
        if "mask" in first:
            return ad
        return msg_lib.pad_adapter(ad, self.cfg.lora.r_max)

    # -- rank adaptation ----------------------------------------------------

    def _target_spectra(self) -> Dict[str, np.ndarray]:
        """Per-target mean singular spectrum of the aggregated ΔW'.

        Straight from the engine when available (it just ran the SVD, so
        Σ is free). When no engine spectrum exists — e.g. a restored
        session that has not aggregated yet — fall back to deriving it
        from the stored factors, normalizing per split: under 'paper' B'
        rows have norm σ, under 'sqrt' both factors carry √σ (so row
        norms of B' are √σ and must be squared) — the same normalization
        per target, so the per-target policy is split-invariant too."""
        if self.last_spectrum is not None:
            return {
                t: np.asarray(s, np.float64).reshape(-1,
                                                     s.shape[-1]).mean(0)
                for t, s in self.last_spectrum.items()}
        out = {}
        for t, ad in self.global_lora.items():
            b = np.asarray(jnp.linalg.norm(ad["B"], axis=-1))  # (L,r)|(r,)
            s = b.reshape(-1, b.shape[-1]).mean(axis=0)
            if self.strategy.split == "sqrt":
                s = s ** 2          # row norms of B' are √σ under 'sqrt'
            out[t] = s
        return out

    def adapt_ranks(self) -> None:
        """Beyond-paper adaptive policy: read the singular spectrum of the
        aggregated ΔW' and pick the smallest rank capturing
        ``spectrum_energy`` of it (``agg_engine.rank_for_energy``).

        Per-client: one rank from the spectra pooled across targets
        (mean σ² — squaring before pooling, as the seed did). With
        ``scfg.per_target_ranks``, each target additionally gets its own
        energy rank from its own spectrum; redistribution masks target t
        to min(r_client, r_target). Works identically in sync rounds and
        async flushes — both call it from the same merge epilogue."""
        spectra = self._target_spectra()
        e, lo, hi = (self.scfg.spectrum_energy, self.scfg.r_min,
                     self.scfg.r_max)
        # rank_for_energy pools leading axes by mean σ² itself — the
        # stacked (T, r) spectra give exactly the mean-over-targets
        # energy cutoff
        r_star = agg_engine.rank_for_energy(
            np.stack(list(spectra.values())), e, lo, hi)
        self.ranks = np.full((self.scfg.num_clients,), r_star, np.int32)
        if self.scfg.per_target_ranks:
            self.target_ranks = {
                t: agg_engine.rank_for_energy(s, e, lo, hi)
                for t, s in spectra.items()}

    def record_routing(self, stats) -> None:
        """A round's routing statistics, as a trainer of a model with
        routed experts returns them (``moe_load`` (..., L, held) and
        ``moe_dropped`` (..., L), per client or for one), into the
        registry: counters ``fed.moe_routed`` (token-expert pairs sent to
        this device's experts) and ``fed.moe_dropped`` (of those, pairs not
        computed), and the gauge
        ``fed.moe_load_max_over_mean`` (the most-loaded held expert's
        pairs over the mean, in the layer where that ratio is largest)."""
        load = np.asarray(stats["moe_load"], np.float64)
        load = load.reshape(-1, *load.shape[-2:]).sum(0)
        self.metrics.counter("fed.moe_routed").inc(int(load.sum()))
        self.metrics.counter("fed.moe_dropped").inc(
            int(np.asarray(stats["moe_dropped"]).sum()))
        mean = np.maximum(load.mean(-1), 1e-9)
        self.metrics.gauge("fed.moe_load_max_over_mean").set(
            float((load.max(-1) / mean).max()))

    # -- accessors -----------------------------------------------------------

    def global_params(self):
        return {**self.base, **self.global_head, "lora": self.global_lora}

    def comm_totals(self) -> Dict[str, int]:
        return {k: int(sum(v)) for k, v in self.comm_log.items()}

    # -- checkpoint / resume -------------------------------------------------

    def save(self, ckpt_dir: str, step: Optional[int] = None) -> str:
        """Persist global factors + masks + ranks + scheduler counters via
        checkpoint/store.py. The rng bit-generator state rides in the JSON
        meta so a restored session replays the identical cohort/key
        sequence. The default step is rounds_done + version so both sync
        rounds AND async flushes advance the checkpoint index (sync never
        touches version, async never touches rounds_done)."""
        from repro.checkpoint import store
        tree = {"global_lora": self.global_lora,
                "global_head": self.global_head,
                "ranks": np.asarray(self.ranks, np.int32)}
        if self.async_state is not None:
            tree["async"] = self._pack_async_state()
        meta = {
            "rounds_done": self.rounds_done,
            "version": self.version,
            "staleness_log": list(map(int, self.staleness_log)),
            "target_ranks": self.target_ranks,
            "strategy": self.strategy.name,
            "rng_state": self.rng.bit_generator.state,
            "comm_log": {k: list(map(int, v))
                         for k, v in self.comm_log.items()},
        }
        return store.save(ckpt_dir, self.rounds_done + self.version
                          if step is None else step, tree, meta)

    def _pack_async_state(self) -> dict:
        """Serialize the live ``BufferedAsync`` state for save().

        The heap is stored in its *list* order — a valid heap list is its
        own heapified form, so the restored list pops in the identical
        order. The K-buffer's ``ClientUpdate``s are stored as their raw
        wire bytes (checkpoint/store.py round-trips bytes leaves), which
        preserves them bit-exactly including any codec encoding."""
        st = self.async_state
        heap = st["heap"]
        return {
            "heap": {
                "t": np.asarray([h[0] for h in heap], np.float64),
                "cid": np.asarray([h[1] for h in heap], np.int64),
                "ver": np.asarray([h[2] for h in heap], np.int64)},
            "pending": {f"{int(cid):08d}": tree
                        for cid, tree in st["pending"].items()},
            "buffer": {f"{i:06d}": u.to_bytes()
                       for i, u in enumerate(st["buffer"])},
        }

    @staticmethod
    def _unpack_async_state(packed: dict) -> dict:
        heap = [(float(t), int(c), int(v))
                for t, c, v in zip(packed["heap"]["t"],
                                   packed["heap"]["cid"],
                                   packed["heap"]["ver"])]
        pending = {int(k): jax.tree.map(jnp.asarray, tree)
                   for k, tree in packed.get("pending", {}).items()}
        buffer = [msg_lib.ClientUpdate.from_bytes(packed["buffer"][k])
                  for k in sorted(packed.get("buffer", {}))]
        return {"heap": heap, "pending": pending, "buffer": buffer}

    @classmethod
    def restore(cls, ckpt_dir: str, cfg: ModelConfig, scfg: ServerConfig,
                base_params, step: Optional[int] = None,
                **session_kwargs) -> "FedSession":
        """Rebuild a session mid-run. The persisted strategy name is
        re-applied unless the caller passes an explicit ``strategy`` —
        a session saved under 'flora' must not silently resume under
        ``scfg.strategy``'s math. ``last_spectrum`` is deliberately not
        persisted: the next ``adapt_ranks`` on a restored session
        exercises the split-normalized factor-norm fallback of
        ``_target_spectra`` until the first post-restore aggregation."""
        from repro.checkpoint import store
        tree, meta = store.restore(ckpt_dir, step)
        if session_kwargs.get("strategy") is None and meta.get("strategy"):
            session_kwargs["strategy"] = meta["strategy"]
        sess = cls(cfg, scfg, base_params, **session_kwargs)
        sess.global_lora = {
            t: {k: jnp.asarray(v) for k, v in ad.items()}
            for t, ad in tree["global_lora"].items()}
        sess.global_head = {k: jnp.asarray(v) for k, v
                            in tree.get("global_head", {}).items()}
        sess.ranks = np.asarray(tree["ranks"], np.int32)
        sess.rounds_done = int(meta["rounds_done"])
        sess.version = int(meta["version"])
        sess.staleness_log = list(meta.get("staleness_log", []))
        tr = meta.get("target_ranks")
        sess.target_ranks = None if tr is None \
            else {t: int(r) for t, r in tr.items()}
        sess.rng.bit_generator.state = meta["rng_state"]
        cl = meta.get("comm_log")
        if cl:
            sess.comm_log = {k: list(v) for k, v in cl.items()}
        if "async" in tree:
            sess.async_state = cls._unpack_async_state(tree["async"])
        return sess
