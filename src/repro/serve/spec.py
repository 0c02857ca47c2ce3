"""Speculative decoding drafters — the cheap half of draft–verify.

The engine's speculative path (``ServeEngine(drafter=...)``) is
*lossless by construction*: whatever a drafter proposes, the verify step
scores every draft position under the target model and commits only the
longest prefix that exactly matches the target's own greedy tokens, plus
the target's next token.  A perfect drafter turns ``spec_k + 1`` decode
dispatches into one; a useless drafter degenerates to one committed
token per dispatch — plain decode at slightly higher FLOPs, never wrong
tokens.  Drafters therefore need no quality guarantee, only a
``propose(engine, active) -> (len(active), spec_k) int32`` method.

Three families live here:

``SelfDrafter`` — the HLoRA-flavoured self-draft: run only the first
``draft_layers`` transformer layers (with each row's *own* adapter
gathered from the registry slabs, so heterogeneous-rank clients draft
through their personalized low-rank path) and read logits off the
shared head.  It reuses the paged cache end-to-end: committed positions
are read through the page table like any decode step, and the draft's
own K/V lands in exactly the slots the verify step overwrites — so a
rejected draft leaves nothing behind that the length mask doesn't
already kill.  One extra jitted step, traced once.

``NGramDrafter`` — prompt-lookup drafting: match the row's trailing
n-gram against its own history (prompt + generated) and propose what
followed the most recent earlier occurrence.  Pure host work, zero
device cost — the free-lunch drafter for templated/repetitive traffic.

``ScriptedDrafter`` — proposes from a per-request token script.  The
test/benchmark harness: scripting the true continuation forces
acceptance ~1 (the speedup ceiling), scripting garbage forces
acceptance 0 (the losslessness floor).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models.transformer import norm
from repro.serve import engine as engine_mod


class SelfDrafter:
    """Shallow layer-subset self-draft over the paged cache.

    ``propose`` runs ``spec_k`` sequential dispatches of a
    ``draft_layers``-deep forward for the whole batch: the cost ratio to
    one full decode step is ~``spec_k * draft_layers / num_layers``, so
    the draft pays for itself whenever acceptance beats that ratio.
    The drafter binds to one engine (its jit cache closes over the
    engine's shapes) and bumps the engine's ``trace_count`` so trace-
    flatness tests cover the draft step too.
    """

    def __init__(self, draft_layers: int = 1):
        if draft_layers < 1:
            raise ValueError(f"draft_layers must be >= 1, got "
                             f"{draft_layers}")
        self.draft_layers = int(draft_layers)
        self._engine = None
        self._step = None

    def _bind(self, engine) -> None:
        if self._engine is engine:
            return
        if self._engine is not None:
            raise RuntimeError("SelfDrafter is bound to another engine "
                               "(its jit cache closes over that "
                               "engine's shapes) — make one per engine")
        if self.draft_layers > engine.cfg.num_layers:
            raise ValueError(
                f"draft_layers {self.draft_layers} exceeds model depth "
                f"{engine.cfg.num_layers}")
        d = self.draft_layers

        def impl(params, slabs, pools, tables, idx, tokens, pos, lens):
            engine.trace_count += 1    # fires at trace time only
            ps = engine.page_size
            p = tables.shape[1]
            x = engine._embed(params, tokens, pos[:, None])
            # Draft positions can run past the row's page table (the
            # verify window is shorter near max_new but the draft loop
            # is fixed-length): those writes go to trash outright —
            # clipping the index instead would alias them onto the
            # row's last live page and corrupt committed KV.
            pageidx = pos // ps
            page = jnp.take_along_axis(tables,
                                       jnp.minimum(pageidx, p - 1)[:, None],
                                       axis=1)[:, 0]
            page = jnp.where((lens > 0) & (pageidx < p), page,
                             engine.kv.trash)
            slot = pos % ps
            layers_d = jax.tree.map(lambda v: v[:d], params["layers"])
            slabs_d = jax.tree.map(lambda v: v[:d], slabs)
            pools_d = {kk: vv[:d] for kk, vv in pools.items()}
            kv_valid = engine._decode_kv_valid(tables, lens)

            def body(carry, xs):
                lp, slab_l, lc = xs
                y, new_lc = engine_mod._layer_decode_paged(
                    carry, lp, slab_l, lc, idx, pos, lens, page, slot,
                    tables, kv_valid, engine.cfg, engine.use_pallas, ps)
                return y, new_lc

            x, new_d = lax.scan(body, x, (layers_d, slabs_d, pools_d))
            x = norm(x, params["final_norm"])
            logits = engine._logits(params, x[:, 0, :])
            new_pools = {
                kk: lax.dynamic_update_slice(
                    pools[kk], new_d[kk].astype(pools[kk].dtype),
                    (0,) * pools[kk].ndim)
                for kk in pools}
            return logits, new_pools

        # Same sharding as the engine's own decode step (identity when
        # the engine is unsharded): the draft reads/writes the same
        # per-shard page sub-pools through the same row blocks.
        self._step = jax.jit(engine._wrap_decode_shaped(impl))
        self._engine = engine

    def propose(self, engine, active) -> np.ndarray:
        self._bind(engine)
        k = engine.spec_k
        props = np.zeros((len(active), k), np.int32)
        # the engine discards proposals past each row's speculative
        # window (min(spec_k, remaining - 1)); don't pay dispatches for
        # columns no row can use — e.g. every request's final dispatch
        # has k_b = 0 and drafts nothing at all
        k_use = max((engine._spec_window(req) for _, req in active),
                    default=0)
        if k_use == 0:
            return props
        cur = np.zeros((engine.max_batch, 1), np.int32)
        pos = np.zeros((engine.max_batch,), np.int32)
        idx = np.zeros((engine.max_batch,), np.int32)
        lens = np.zeros((engine.max_batch,), np.int32)
        for _, (i, req) in enumerate(active):
            cur[i, 0] = req["out"][-1]
            pos[i] = req["t"]
            idx[i] = req["slot"]
            lens[i] = req["t"] + 1
        alive = (lens > 0).astype(np.int32)
        rec = engine.rec
        # one span per draft burst on the engine track (the verify span
        # starts after this returns, so that track never nests); its
        # steps go on a track of their own
        with rec.span("serve.draft", engine._engine_track, k=int(k_use),
                      batch=len(active)):
            for step in range(k_use):
                with rec.span("serve.draft_step",
                              f"{engine.name}/draft"):
                    logits, engine.kv.pools = self._step(
                        engine.params, engine.registry.slabs(),
                        engine.kv.pools, jnp.asarray(engine.kv.tables),
                        jnp.asarray(idx), jnp.asarray(cur),
                        jnp.asarray(pos), jnp.asarray(lens))
                    nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
                for j, (i, _) in enumerate(active):
                    props[j, step] = nxt[i]
                cur = nxt[:, None].copy()
                pos = pos + alive
                lens = lens + alive
        return props


class NGramDrafter:
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the row's trailing ``n``-gram in its
    own prompt + output history; fall back to repeating the last token
    when no earlier occurrence exists (a wrong draft costs nothing)."""

    def __init__(self, n: int = 2):
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        self.n = int(n)

    def propose(self, engine, active) -> np.ndarray:
        k = engine.spec_k
        props = np.zeros((len(active), k), np.int32)
        for j, (_, req) in enumerate(active):
            hist = np.concatenate([np.asarray(req["prompt"], np.int32),
                                   np.asarray(req["out"], np.int32)])
            props[j] = self._lookup(hist, k)
        return props

    def _lookup(self, hist: np.ndarray, k: int) -> np.ndarray:
        out = np.full((k,), int(hist[-1]), np.int32)
        n = self.n
        if hist.size <= n:
            return out
        tail = hist[-n:]
        for start in range(hist.size - n - 1, -1, -1):
            if (hist[start:start + n] == tail).all():
                follow = hist[start + n:start + n + k]
                out[:follow.size] = follow
                break
        return out


class ScriptedDrafter:
    """Proposes from per-request scripts of future output tokens,
    indexed by the tokens already generated — ``set(uid, script)`` with
    the request's true greedy continuation gives forced-accept, any
    never-matching script gives forced-reject. Rows without a script
    propose zeros (which may or may not match — fine either way)."""

    def __init__(self, scripts: Optional[Dict[str, np.ndarray]] = None):
        self.scripts: Dict[str, np.ndarray] = {}
        for uid, toks in (scripts or {}).items():
            self.set(uid, toks)

    def set(self, uid: str, tokens) -> None:
        self.scripts[uid] = np.asarray(tokens, np.int32).reshape(-1)

    def propose(self, engine, active) -> np.ndarray:
        k = engine.spec_k
        props = np.zeros((len(active), k), np.int32)
        for j, (_, req) in enumerate(active):
            script = self.scripts.get(req["uid"])
            if script is None:
                continue
            done = len(req["out"])
            nxt = script[done:done + k]
            props[j, :nxt.size] = nxt
        return props
