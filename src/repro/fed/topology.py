"""Hierarchical two-tier aggregation: edge aggregators + a root session.

The cross-silo topology: clients upload to their *edge* aggregator, edges
forward to the root, the root merges — the shape FedML's FedLLM pipeline
deploys and the natural way to scale a sampled population beyond one
server's fan-in. Both tiers drive the *same* ``AggregationStrategy``
objects and the same measured wire format; the topology plugs into
``SyncRound(topology=...)`` and only ever calls session public methods.

Two edge modes:

``stack``   (default, lossless) Each edge concentrates its cohort's
            serialized ``ClientUpdate``s into one ``EdgeAggregate``
            message, verbatim. The root reassembles the per-client trees
            in original cohort order and runs the unchanged flat
            ``aggregate_round`` — so with lossless codec settings the
            result is **bit-identical** to flat aggregation (golden
            test, naive + hlora): same bytes in, same stacked tree, same
            single engine call. What the hierarchy buys is fan-in (the
            root sees E messages instead of K) — edge→root bytes equal
            the sum of client bytes plus E small headers.

``engine``  (weight-correct, lossy for SVD strategies) Each edge merges
            its cohort with the session's strategy/engine at cohort-
            local weights ``n_i/n_e``, ships ONE pre-merged r_max update,
            and the root merges the E edge aggregates at weights
            ``n_e/Σn_e`` — the nested weighted mean equals the flat
            weighted mean, so linear strategies (naive) match flat to
            float tolerance while reconstruct+SVD strategies get the
            standard hierarchical approximation. This is the mode that
            actually *shrinks* edge→root traffic (E messages of one
            adapter each, codec-compressible).

Wire accounting flows through the session's ``_log_comm`` choke point:
client→edge bytes land as one consolidated ``uplink`` row (same row the
flat path writes, so history/bench semantics are unchanged) and each
edge→root message lands under ``edge<i>_uplink`` with its own
``fed.edge<i>`` obs track (per-edge spans + byte samples).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed import messages as msg_lib
from repro.fed.session import PARTS_TRACK, SERVER_TRACK, client_slice


def _stack_trees(per_client, heads):
    """Stack per-client device trees/heads into cohort arrays."""
    out = {t: {leaf: jnp.stack([c[t][leaf] for c in per_client])
               for leaf in ("A", "B", "mask")}
           for t in per_client[0]}
    heads_st = jax.tree.map(lambda *xs: jnp.stack(xs), *heads) \
        if heads and heads[0] else {}
    return out, heads_st


@dataclass
class HierarchicalTopology:
    """Two-tier edge/root aggregation plan for one sync round."""

    num_edges: int = 2
    #: how cohort members map to edges: ``contiguous`` (array_split),
    #: ``round_robin`` (position modulo E), ``hash`` (client id modulo E
    #: — stable across rounds, like a geo assignment)
    assignment: str = "contiguous"
    edge_mode: str = "stack"        # stack | engine

    def __post_init__(self):
        if self.num_edges < 1:
            raise ValueError(f"num_edges must be >= 1, got {self.num_edges}")
        if self.assignment not in ("contiguous", "round_robin", "hash"):
            raise ValueError(f"unknown assignment {self.assignment!r}")
        if self.edge_mode not in ("stack", "engine"):
            raise ValueError(f"unknown edge_mode {self.edge_mode!r}")

    def assign(self, cohort: np.ndarray) -> List[np.ndarray]:
        """Partition cohort *positions* (indices into the cohort array)
        into per-edge groups; every position lands in exactly one edge."""
        pos = np.arange(len(cohort))
        if self.assignment == "contiguous":
            return [np.asarray(g) for g in np.array_split(pos,
                                                          self.num_edges)]
        if self.assignment == "round_robin":
            return [pos[e::self.num_edges] for e in range(self.num_edges)]
        cids = np.asarray(cohort, np.int64)
        return [pos[cids % self.num_edges == e]
                for e in range(self.num_edges)]

    # -- the round's collect+aggregate, replacing the flat path ------------

    def aggregate(self, session, cohort: np.ndarray, trained_tree,
                  trained_heads=None) -> None:
        """Collect the trained cohort through the two-tier wire path and
        run the root merge. Mirrors ``collect_updates`` +
        ``aggregate_round`` exactly in 'stack' mode (bit-identical)."""
        cohort = np.asarray(cohort)
        groups = self.assign(cohort)
        if self.edge_mode == "stack":
            self._aggregate_stack(session, cohort, groups, trained_tree,
                                  trained_heads)
        else:
            self._aggregate_engine(session, cohort, groups, trained_tree,
                                   trained_heads)

    def _aggregate_stack(self, session, cohort, groups, trained_tree,
                         trained_heads) -> None:
        rec = session.rec
        if not session.track_comm:
            for e, pos in enumerate(groups):
                session._log_comm(f"edge{e}_uplink", 0,
                                  track=f"fed.edge{e}")
            session._log_comm("uplink", 0)
            session.aggregate_round(trained_tree, cohort,
                                    stacked_heads=trained_heads)
            return
        k = len(cohort)
        stack = msg_lib.CohortStack(k, session.cfg.lora.r_max)
        uplink_total = 0
        with rec.span("fed.collect", SERVER_TRACK, cohort=k,
                      edges=len(groups)):
            host, host_heads = session.to_host((trained_tree,
                                                trained_heads))
            for e, pos in enumerate(groups):
                if len(pos) == 0:
                    continue
                track = f"fed.edge{e}"
                # the edge's bytes land as a counter sample on its track
                with rec.span("fed.edge_forward", track, clients=len(pos)):
                    updates = []
                    for i in pos:
                        sl, h = client_slice(host, host_heads, int(i))
                        updates.append(session.make_update(
                            int(cohort[i]), sl, session.version, h,
                            log=False))
                    uplink_total += sum(u.num_bytes for u in updates)
                    agg = msg_lib.EdgeAggregate(edge_id=e, updates=updates)
                    rt = msg_lib.EdgeAggregate.from_bytes(agg.to_bytes())
                    session._log_comm(f"edge{e}_uplink", agg.num_bytes,
                                      track=track)
                # each client's decoded update lands at its cohort
                # position — the same stack the flat collect_updates builds
                for i, upd in zip(pos, rt.updates):
                    stack.put(int(i), upd.adapter, upd.head)
            session._log_comm("uplink", uplink_total)
            with rec.span("fed.restack", PARTS_TRACK, cohort=k):
                out, heads_st = session.to_device((stack.tree,
                                                   stack.heads))
        session.aggregate_round(
            out, cohort,
            stacked_heads=(heads_st or None)
            if trained_heads is not None else None)

    @staticmethod
    def _edge_merge(session, e: int, track: str, tree_e, heads_e, eta_e,
                    n_e):
        """One edge's pre-merge at cohort-local weights, shipped to the
        root as ONE r_max update (the message that actually shrinks root
        fan-in bytes); returns what the root unpacks."""
        r_max = session.cfg.lora.r_max
        full = {t: jnp.ones_like(ad["mask"][:1])
                for t, ad in tree_e.items()}
        out, _spec = session.engine(
            tree_e, eta_e, session.cfg.lora.alpha,
            **session.strategy.engine_kwargs(), new_masks=full)
        merged = {t: {"A": ad["A"][0], "B": ad["B"][0],
                      "mask": ad["mask"][0]}
                  for t, ad in out.items()}
        head_m = {}
        if heads_e:
            head_m = jax.tree.map(
                lambda x: jnp.tensordot(
                    eta_e, x.astype(jnp.float32),
                    axes=1).astype(x.dtype), heads_e)
        if not session.track_comm:
            session._log_comm(f"edge{e}_uplink", 0, track=track)
            return merged, head_m
        upd_e = msg_lib.ClientUpdate(
            client_id=e, start_version=session.version,
            num_examples=int(n_e.sum()),
            adapter=msg_lib.truncate_adapter(
                merged, {t: r_max for t in merged}),
            head={kk: np.asarray(v) for kk, v in head_m.items()},
            codec=session.codec)
        rt = msg_lib.ClientUpdate.from_bytes(upd_e.to_bytes())
        session._log_comm(f"edge{e}_uplink", rt.num_bytes, track=track)
        return rt.unpack(r_max)

    def _aggregate_engine(self, session, cohort, groups, trained_tree,
                          trained_heads) -> None:
        rec = session.rec
        r_max = session.cfg.lora.r_max
        edge_trees, edge_heads, edge_sizes = [], [], []
        uplink_total = 0
        with rec.span("fed.collect", SERVER_TRACK, cohort=len(cohort),
                      edges=len(groups)):
            for e, pos in enumerate(groups):
                if len(pos) == 0:
                    continue
                track = f"fed.edge{e}"
                # client → edge: the same measured per-client updates the
                # flat path collects (consolidated into the uplink row)
                per, hds = [], []
                for i in pos:
                    sl, h = client_slice(trained_tree, trained_heads,
                                         int(i))
                    if session.track_comm:
                        upd = msg_lib.ClientUpdate.from_bytes(
                            session.make_update(int(cohort[i]), sl,
                                                session.version, h,
                                                log=False).to_bytes())
                        uplink_total += upd.num_bytes
                        tree, head = upd.unpack(r_max)
                    else:
                        tree, head = sl, (h or {})
                    per.append(tree)
                    hds.append(head)
                tree_e, heads_e = _stack_trees(per, hds)
                sub = cohort[np.asarray(pos)]
                n_e = session.client_sizes[sub].astype(np.float64)
                eta_e = jnp.asarray(n_e / n_e.sum(), jnp.float32)
                with rec.span("fed.edge_merge", track, clients=len(pos),
                              examples=int(n_e.sum())):
                    tree_r, head_r = self._edge_merge(
                        session, e, track, tree_e, heads_e, eta_e, n_e)
                edge_trees.append(tree_r)
                edge_heads.append(head_r)
                edge_sizes.append(float(n_e.sum()))
            session._log_comm("uplink", uplink_total)
        w = np.asarray(edge_sizes, np.float64)
        out, heads_st = _stack_trees(edge_trees, edge_heads)
        session.aggregate_round(
            out, cohort,
            stacked_heads=(heads_st or None)
            if trained_heads is not None else None,
            weights=w / w.sum())
