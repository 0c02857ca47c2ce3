"""Multi-tenant serving engine: continuous batching over per-request LoRA,
with a paged KV cache and chunked prefill.

One jitted decode step serves the whole batch. Each of the ``max_batch``
request rows carries its own adapter-slot index into the registry slabs;
inside every layer the LoRA path is the BGMV gather

    y[i] = x[i] @ W0 + scale[idx[i]] · (x[i] @ A[idx[i]]) @ B[idx[i]]

(Pallas ``kernels/bgmv.py`` on TPU, the gather-einsum oracle elsewhere).

KV state is **paged** by default (``serve/pages.py``): rows own page
lists in a global pool instead of dense ``(max_seq, Hkv, Dh)`` strips,
so admission is gated by *free pages* — what traffic actually uses —
rather than by the worst-case ``max_seq``. The scheduler defers
admission while the pool is dry, extends a row's page list as its decode
crosses page boundaries, and preempts the youngest rows (their requests
re-queue and replay — greedy decode is deterministic) when an extension
cannot be satisfied. Decode attention reads pages through the table in
the Pallas kernel (``kernels/paged_attn.py``, TPU); the plain path reads
each layer's pool once in place under a per-row validity mask built from
the tables once per step.

Prefill is **chunked**: a second jitted step pushes ``prefill_chunk``
prompt tokens at a time through full attention at absolute offset
``q_offset = pos0`` (``kernels/flash_attn.py`` carries the offset in
scalar-prefetch SMEM on TPU), writing K/V straight into the row's pages
— versus the seed's token-at-a-time teacher forcing, one device dispatch
per prompt *chunk* instead of per prompt token. Padded chunk tail tokens
write to the pool's trash page.

Decode can run **speculatively** (``drafter=...``): a drafter proposes
up to ``spec_k`` tokens per row (``serve/spec.py``), a third jitted
step scores all ``spec_k + 1`` positions in one dispatch through the
multi-query-token paged read (``kernels/verify.py``), and each row
commits the longest draft prefix that exactly matches the model's own
greedy tokens plus the model's next token — lossless by construction,
1 to ``spec_k + 1`` committed tokens per dispatch. Rejected suffixes
roll their pages back via ``PagedKV.truncate``.

Everything is value updates against fixed shapes — page tables, page
extensions, admissions, hot-swaps, speculative windows, rollbacks — so
``trace_count`` stays flat at one trace per jitted step (decode +
prefill + verify) for the engine's lifetime.

``kv_mode="dense"`` keeps the PR-2 dense ring cache as a fallback; its
insert path *drops* writes past the ring instead of silently wrapping
(which corrupted attention for any row outliving its ring), and the
scheduler raises before that can happen.
"""
from __future__ import annotations

import math
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_rules
from repro.models.common import (_act, _repeat_kv, attention, init_kv_cache,
                                 rope, sinusoidal_positions)
from repro.models.transformer import norm
from repro.obs import NULL_RECORDER, MetricsRegistry
from repro.serve.pages import PagedKV


def _counter_view(suffix: str):
    """Property exposing a registry counter as a plain int attribute.

    The engine's historical counters (``trace_count``, ``steps``, ...)
    stay readable/writable exactly as before — including the
    ``self.trace_count += 1`` side effects that fire at trace time
    inside the jitted bodies — while the values live in the metrics
    registry where exporters and benches read them. Metric names are
    prefixed by the engine's ``name`` (``serve.traces`` by default), so
    engines sharing one registry keep disjoint namespaces — and one
    engine's ``__init__`` zeroing its counters cannot wipe another's."""
    def _get(self):
        return self.metrics.counter(f"{self.name}.{suffix}").value

    def _set(self, v):
        self.metrics.counter(f"{self.name}.{suffix}").value = int(v)

    return property(_get, _set)


def _gauge_view(suffix: str):
    def _get(self):
        return self.metrics.gauge(f"{self.name}.{suffix}").value

    def _set(self, v):
        self.metrics.gauge(f"{self.name}.{suffix}").set(int(v))

    return property(_get, _set)


def _apply_slab_lora(x, w0, slab, idx, alpha, use_pallas: bool):
    """x: (B, S, d_in) -> x @ W0 + per-row gathered LoRA delta.

    S == 1 (decode) rides the BGMV kernel on TPU; S > 1 (chunked prefill,
    batch 1 there) uses the gather-einsum — one adapter gather for the
    whole chunk."""
    y = x @ w0
    if slab is None:
        return y
    a, b, m = slab["A"], slab["B"], slab["mask"]     # (S,d,r) (S,r,o) (S,r)
    am = a * m[:, None, :]                            # dead directions -> 0
    scale = alpha / jnp.maximum(jnp.sum(m, axis=-1), 1.0)          # (S,)
    if use_pallas and x.shape[1] == 1:
        from repro.kernels import ops
        lo = ops.bgmv(x[:, 0, :], am, b, idx)[:, None, :]
    else:
        lo = jnp.einsum("bsr,bro->bso",
                        jnp.einsum("bsd,bdr->bsr", x, am[idx]), b[idx])
    return y + (scale[idx][:, None, None] * lo).astype(y.dtype)


def _cache_insert_rows(lc, k_new, v_new, pos):
    """Per-row dense-ring insert: row i's token goes to slot pos[i].

    Positions at or past the ring (pos >= slots) are **dropped**, not
    wrapped: wrapping overwrote the row's oldest live entries while the
    validity mask still reported them current — silently corrupted
    attention for any row that outlived its ring. The host scheduler
    raises before this can happen (see ``step_batch``); ``mode='drop'``
    makes the traced path fail safe rather than fail wrong."""
    rows = jnp.arange(pos.shape[0])
    return {
        "k": lc["k"].at[rows, pos].set(k_new[:, 0], mode="drop"),
        "v": lc["v"].at[rows, pos].set(v_new[:, 0], mode="drop"),
        "pos": lc["pos"].at[rows, pos].set(pos, mode="drop"),
    }


# ---------------------------------------------------------------------------
# Shared per-layer blocks (decode and prefill differ only in KV handling)
# ---------------------------------------------------------------------------

def _layer_qkv(x, lp, slab, idx, pos, cfg: ModelConfig, use_pallas):
    """norm -> q/k/v projections with per-row LoRA -> heads + RoPE.
    x: (B, S, d), pos: (B, S) absolute positions."""
    alpha = cfg.lora.alpha
    bsz, s, _ = x.shape
    hd = cfg.resolved_head_dim
    ap = lp["attn"]
    h = norm(x, lp["ln1"])
    q = _apply_slab_lora(h, ap["wq"], slab.get("q"), idx, alpha, use_pallas)
    k = _apply_slab_lora(h, ap["wk"], slab.get("k"), idx, alpha, use_pallas)
    v = _apply_slab_lora(h, ap["wv"], slab.get("v"), idx, alpha, use_pallas)
    if cfg.use_bias:
        q, k, v = q + ap.get("bq", 0.0), k + ap.get("bk", 0.0), \
            v + ap.get("bv", 0.0)
    q = q.reshape(bsz, s, cfg.num_heads, hd)
    k = k.reshape(bsz, s, cfg.num_kv_heads, hd)
    v = v.reshape(bsz, s, cfg.num_kv_heads, hd)
    if cfg.rope_theta > 0:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    return h, q, k, v


def _layer_out(x, o, lp, slab, idx, cfg: ModelConfig, use_pallas):
    """Attention output projection + residual + LoRA'd MLP block."""
    alpha = cfg.lora.alpha
    ap = lp["attn"]
    y = _apply_slab_lora(o, ap["wo"], slab.get("o"), idx, alpha, use_pallas)
    if cfg.use_bias and "bo" in ap:
        y = y + ap["bo"]
    x = x + y
    h2 = norm(x, lp["ln2"])
    mp = lp["mlp"]
    act = _act(cfg.activation)
    u = _apply_slab_lora(h2, mp["w1"], slab.get("w1"), idx, alpha, use_pallas)
    if cfg.use_bias and "b1" in mp:
        u = u + mp["b1"]
    u = act(u)
    if "w3" in mp:
        u = u * _apply_slab_lora(h2, mp["w3"], slab.get("w3"), idx, alpha,
                                 use_pallas)
    y = _apply_slab_lora(u, mp["w2"], slab.get("w2"), idx, alpha, use_pallas)
    if cfg.use_bias and "b2" in mp:
        y = y + mp["b2"]
    return x + y


def _layer_decode_dense(x, lp, slab, lc, idx, pos, cfg: ModelConfig,
                        use_pallas: bool):
    """One token through one layer against the dense ring cache."""
    bsz = x.shape[0]
    hd = cfg.resolved_head_dim
    _, q, k, v = _layer_qkv(x, lp, slab, idx, pos[:, None], cfg, use_pallas)
    lc = _cache_insert_rows(lc, k, v, pos)
    # Validity-masked attention: each row sees exactly its own cached
    # prefix (stale slots are pos=-1, recycled rows were reset) — the
    # causal structure is in the mask, not a shared scalar position.
    valid = (lc["pos"] >= 0) & (lc["pos"] <= pos[:, None])
    o = attention(q, lc["k"], lc["v"], causal=False, window=None,
                  kv_positions=lc["pos"], kv_valid=valid)
    o = o.reshape(bsz, 1, cfg.num_heads * hd)
    return _layer_out(x, o, lp, slab, idx, cfg, use_pallas), lc


def pool_kv_valid(tables, lens, pool_pages: int, page_size: int):
    """(B, pool_pages * page_size) bool: which pool slots row ``b``
    attends. Row ``b`` holds pool page ``p`` at table index
    ``page_pos[b, p]`` (-1 if not at all), so slot ``s`` of that page is
    position ``page_pos * page_size + s``, valid below ``lens[b]``. A
    live page is never owned twice; the trash page can sit at several
    table indices of a row, but all of them at positions ``>= lens``, so
    whichever one the scatter keeps is masked."""
    bsz, p = tables.shape
    page_pos = jnp.full((bsz, pool_pages), -1, jnp.int32).at[
        jnp.arange(bsz)[:, None], tables].max(
        jnp.broadcast_to(jnp.arange(p, dtype=jnp.int32), (bsz, p)))
    kv_pos = page_pos[:, :, None] * page_size + jnp.arange(page_size)
    valid = (page_pos[:, :, None] >= 0) & (kv_pos < lens[:, None, None])
    return valid.reshape(bsz, pool_pages * page_size)


def pool_decode_attention(q, k_pool, v_pool, kv_valid):
    """One decode token per row against a whole layer pool, in place.
    q: (B, H, Dh); k_pool/v_pool: (N, ps, Hkv, Dh); kv_valid: (B, N*ps)
    from ``pool_kv_valid`` -> (B, H, Dh) in the pool's dtype; rows with
    no valid slot emit zeros. Scores, softmax and the output accumulate
    in f32 at ``attention``'s precision, over exact f32 up-casts of the
    stored K and V, which XLA fuses into the products: no f32 copy of
    the pool is made, and the pool broadcasts over the rows."""
    b, h, dh = q.shape
    n, ps, hkv, _ = k_pool.shape
    f32 = jnp.float32
    kp = k_pool.reshape(n * ps, hkv, dh).astype(f32)
    vp = v_pool.reshape(n * ps, hkv, dh).astype(f32)
    qg = q.reshape(b, hkv, h // hkv, dh).astype(f32)   # head h: KV h // G
    logits = jnp.einsum("bkgd,skd->bkgs", qg, kp) * (1.0 / math.sqrt(dh))
    logits = jnp.where(kv_valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,skd->bkgd", probs, vp)
    out = out * jnp.any(kv_valid, axis=-1)[:, None, None, None]
    return out.reshape(b, h, dh).astype(v_pool.dtype)


def _layer_decode_paged(x, lp, slab, lc, idx, pos, lens, page, slot,
                        tables, kv_valid, cfg: ModelConfig,
                        use_pallas: bool, page_size: int):
    """One token through one layer against the paged pool.
    page/slot: (B,) precomputed write targets (trash for inactive rows);
    tables: (B, P) page tables; lens: (B,) valid tokens incl. this one;
    kv_valid: the step's ``pool_kv_valid`` for plain attention, which
    reads the pool in place; None under the Pallas kernel."""
    bsz = x.shape[0]
    hd = cfg.resolved_head_dim
    _, q, k, v = _layer_qkv(x, lp, slab, idx, pos[:, None], cfg, use_pallas)
    lck = lc["k"].at[page, slot].set(k[:, 0])
    lcv = lc["v"].at[page, slot].set(v[:, 0])
    if use_pallas:
        from repro.kernels import ops
        o = ops.paged_attention(q[:, 0], lck, lcv, tables, lens,
                                page_size=page_size)[:, None]
    else:
        o = pool_decode_attention(q[:, 0], lck, lcv, kv_valid)[:, None]
    o = o.reshape(bsz, 1, cfg.num_heads * hd)
    return _layer_out(x, o, lp, slab, idx, cfg, use_pallas), {"k": lck,
                                                              "v": lcv}


def _layer_verify_paged(x, lp, slab, lc, idx, tpos, lens, page, slot,
                        tables, pos0, cfg: ModelConfig, use_pallas: bool,
                        page_size: int):
    """A window of S speculative tokens per row through one layer.
    x: (B, S, d); tpos: (B, S) absolute positions (pos0[b] + i);
    page/slot: (B, S) write targets (invalid tail tokens and inactive
    rows -> trash); tables: (B, P); lens: (B,) valid tokens *including*
    the window (0 for inactive rows); pos0: (B,) window start — the
    per-row causal frontier of the multi-token paged read."""
    bsz, s, _ = x.shape
    hd = cfg.resolved_head_dim
    _, q, k, v = _layer_qkv(x, lp, slab, idx, tpos, cfg, use_pallas)
    lck = lc["k"].at[page, slot].set(k)
    lcv = lc["v"].at[page, slot].set(v)
    if use_pallas:
        from repro.kernels import ops
        o = ops.paged_verify_attention(q, lck, lcv, tables, lens, pos0,
                                       page_size=page_size)
    else:
        from repro.kernels import ref
        o = ref.paged_verify_ref(q, lck, lcv, tables, lens, pos0)
    o = o.reshape(bsz, s, cfg.num_heads * hd)
    return _layer_out(x, o, lp, slab, idx, cfg, use_pallas), {"k": lck,
                                                              "v": lcv}


def _layer_prefill_paged(x, lp, slab, lc, idx, tpos, page, slot, table_row,
                         pos0, cfg: ModelConfig, use_pallas: bool,
                         page_size: int):
    """A chunk of one row's prompt through one layer. x: (1, C, d);
    tpos: (1, C) absolute positions; page/slot: (C,) write targets
    (padded tail tokens -> trash page); table_row: (1, P)."""
    c = x.shape[1]
    hd = cfg.resolved_head_dim
    _, q, k, v = _layer_qkv(x, lp, slab, idx, tpos, cfg, use_pallas)
    lck = lc["k"].at[page, slot].set(k[0])
    lcv = lc["v"].at[page, slot].set(v[0])
    p = table_row.shape[1]
    kk = lck[table_row].reshape(1, p * page_size, cfg.num_kv_heads, hd)
    vv = lcv[table_row].reshape(1, p * page_size, cfg.num_kv_heads, hd)
    if use_pallas:
        from repro.kernels import ops
        groups = cfg.num_heads // cfg.num_kv_heads
        kk = _repeat_kv(kk, groups)
        vv = _repeat_kv(vv, groups)
        # flash blocks must tile Sq/Skv exactly; page-pool capacities are
        # not always multiples of 256 (e.g. 33 pages x 8 slots)
        skv = p * page_size
        bq = max(d for d in range(1, min(256, c) + 1) if c % d == 0)
        bk = max(d for d in range(1, min(256, skv) + 1) if skv % d == 0)
        o = ops.flash_attention(q, kk, vv, causal=True, q_offset=pos0,
                                block_q=bq, block_k=bk)
    else:
        # Causal at absolute offset: stale/trash slots all sit at
        # positions > the chunk's last valid q position, so the causal
        # mask alone excludes them.
        kv_pos = jnp.arange(p * page_size)[None, :]
        o = attention(q, kk, vv, causal=True, window=None, q_offset=pos0,
                      kv_positions=kv_pos)
    o = o.reshape(1, c, cfg.num_heads * hd)
    return _layer_out(x, o, lp, slab, idx, cfg, use_pallas), {"k": lck,
                                                              "v": lcv}


class ServeEngine:
    """Continuous-batching multi-LoRA greedy decoder over a paged KV cache.

    ``max_batch`` request rows share one jitted decode step (and one
    jitted prefill step) whose caches key on (batch, page, slab, param)
    shapes only — request churn, page churn, and adapter hot-swaps never
    recompile. Greedy sampling; the scheduler is host-side (admission,
    paging, preemption, token routing, finish/recycle), everything
    per-token is on device.

    kv_mode="paged" (default): a global page pool; per-request capacity
    is ``ceil((prompt + max_new) / page_size)`` pages, admission waits
    for free pages, decode extends page lists in place, and prompt
    prefill runs ``prefill_chunk`` tokens per dispatch.
    kv_mode="dense": the PR-2 per-row ring cache (one ``max_seq`` strip
    per row, token-at-a-time prefill) — the memory/latency baseline.
    """

    def __init__(self, params, cfg: ModelConfig, registry, *,
                 max_batch: int = 8, max_seq: int = 128,
                 kv_mode: str = "paged", page_size: int = 8,
                 num_pages: Optional[int] = None,
                 prefill_chunk: int = 16,
                 drafter=None, spec_k: int = 4,
                 use_pallas: Optional[bool] = None,
                 cache_dtype=jnp.float32,
                 mesh=None,
                 recorder=None, metrics: Optional[MetricsRegistry] = None,
                 slo_ttft_s: Optional[Dict[str, float]] = None,
                 name: str = "serve"):
        if cfg.arch_type not in ("dense", "vlm"):
            raise NotImplementedError(
                f"serving supports the dense transformer family, got "
                f"{cfg.arch_type!r}")
        if cfg.num_experts:
            raise NotImplementedError("MoE serving not wired yet")
        if kv_mode not in ("paged", "dense"):
            raise ValueError(f"unknown kv_mode {kv_mode!r}")
        if drafter is not None and kv_mode != "paged":
            raise ValueError(
                "speculative decode needs the paged KV cache (rollback "
                "is a page-table operation); kv_mode='dense' has no "
                "draft-verify path")
        if drafter is not None and spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.mesh = mesh_lib.auto_axes(mesh)
        self.num_shards = mesh_lib.data_axis_size(mesh)
        if self.num_shards > 1 and kv_mode != "paged":
            raise ValueError(
                "mesh-sharded serving needs the paged KV cache "
                "(per-device page sub-pools); kv_mode='dense' is "
                "single-device only")
        if self.num_shards > 1 and max_batch % self.num_shards:
            raise ValueError(
                f"max_batch {max_batch} must divide over the mesh's "
                f"{self.num_shards} data-axis devices")
        self.params = params
        self.cfg = cfg
        self.registry = registry
        self.max_batch = int(max_batch)
        self.max_seq = int(max_seq)
        self.kv_mode = kv_mode
        self.drafter = drafter
        self.spec_k = int(spec_k)
        if use_pallas is None:
            from repro.kernels import ops
            use_pallas = ops.on_tpu()
        self.use_pallas = bool(use_pallas)
        # Observability: ``rec`` defaults to the no-op singleton (hot
        # paths guard clock reads with ``if rec.enabled:``); ``metrics``
        # is always on — counter views below write through to it.
        self.rec = recorder if recorder is not None else NULL_RECORDER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.name = str(name)
        self._engine_track = f"{self.name}/engine"
        # Per-class TTFT targets for SLO attainment accounting
        # ({class: seconds}); classes without a target count as attained.
        # Attainment needs TTFT, TTFT needs the clock — so the counters
        # move only while recording is enabled (observe-only: nothing
        # schedules differently by class yet).
        self.slo_ttft_s: Dict[str, float] = dict(slo_ttft_s or {})
        self._slo_classes: set = set()
        self.trace_count = 0
        if kv_mode == "paged":
            self.page_size = int(page_size)
            pages_per_row = -(-self.max_seq // self.page_size)
            if num_pages is None:
                # Same worst-case capacity as the dense cache; the win
                # comes from sizing num_pages to *traffic* instead.
                num_pages = self.max_batch * pages_per_row
            self.kv = PagedKV(cfg.num_layers, int(num_pages),
                              self.page_size, pages_per_row,
                              self.max_batch, cfg.num_kv_heads,
                              cfg.resolved_head_dim, dtype=cache_dtype,
                              num_shards=self.num_shards,
                              metrics=self.metrics,
                              name=f"{self.name}.pages")
            self.prefill_chunk = max(1, int(prefill_chunk))
            if self.num_shards > 1:
                self._place_state()
                step, verify, prefill = self._shard_mapped_steps()
            else:
                step, verify, prefill = (self._paged_step_impl,
                                         self._verify_impl,
                                         self._prefill_impl)
            self._step = jax.jit(step)
            self._prefill = jax.jit(prefill)
            self._verify = jax.jit(verify)
            # KV slots decode attention reads per layer: plain attention
            # reads each shard's sub-pool (its trash page included) in
            # place, the Pallas kernel every row's table.
            self.decode_kv_slots = self.kv.num_shards * self.page_size * (
                self.kv.rows_per_shard * pages_per_row if self.use_pallas
                else self.kv.pages_per_shard + 1)
        else:
            self.cache = init_kv_cache(cfg.num_layers, self.max_batch,
                                       self.max_seq, cfg.num_kv_heads,
                                       cfg.resolved_head_dim,
                                       dtype=cache_dtype)
            self._step = jax.jit(self._dense_step_impl)
            self._reset = jax.jit(self._reset_impl)
        self._queue: deque = deque()
        self._rows: List[Optional[dict]] = [None] * self.max_batch
        self._done: Dict[str, np.ndarray] = {}
        self._uid = 0
        self.steps = 0
        self.tokens_generated = 0
        self.prefill_calls = 0
        self.prefill_tokens = 0
        self.deferrals = 0
        self.preemptions = 0
        # speculative-decode counters (stay 0 without a drafter)
        self.spec_dispatches = 0
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.rollback_pages = 0
        # distinct adapter slots among active rows at the last paged
        # dispatch — rows are sorted/grouped by slot before the BGMV
        # gather (the first move toward SGMV tile reuse)
        self.bgmv_groups = 0

    # The historical public counters, consolidated onto the metrics
    # registry as thin views (``spec_stats`` and every existing caller
    # read identical values through these).
    trace_count = _counter_view("traces")
    steps = _counter_view("steps")
    tokens_generated = _counter_view("tokens")
    prefill_calls = _counter_view("prefill_calls")
    prefill_tokens = _counter_view("prefill_tokens")
    deferrals = _counter_view("deferrals")
    preemptions = _counter_view("preemptions")
    spec_dispatches = _counter_view("spec.dispatches")
    drafted_tokens = _counter_view("spec.drafted")
    accepted_tokens = _counter_view("spec.accepted")
    rollback_pages = _counter_view("spec.rollback_pages")
    bgmv_groups = _gauge_view("bgmv_groups")
    decode_kv_slots = _gauge_view("decode_kv_slots")

    # -- request tracks ------------------------------------------------------

    def _track(self, req: dict) -> str:
        return f"{self.name}/{req['uid']}"

    def _note_first_token(self, req: dict) -> None:
        """First generated token: derive TTFT against the submit stamp,
        and settle the request's SLO-class attainment (TTFT is the
        class-gated latency; a class with no configured target counts
        as attained, so uninstrumented classes still get traffic
        counts)."""
        if "_ts" not in req or "_ttft" in req:
            return
        t = self.rec.now()
        req["_ttft"] = t - req["_ts"]
        self.metrics.histogram(f"{self.name}.ttft_s").observe(req["_ttft"])
        self.rec.instant("first_token", self._track(req),
                         ttft_s=req["_ttft"])
        cls = req.get("slo")
        if cls is not None:
            self._slo_classes.add(cls)
            self.metrics.histogram(
                f"{self.name}.ttft_s.{cls}").observe(req["_ttft"])
            self.metrics.counter(f"{self.name}.slo.{cls}.total").inc()
            target = self.slo_ttft_s.get(cls)
            if target is None or req["_ttft"] <= target:
                self.metrics.counter(f"{self.name}.slo.{cls}.ok").inc()
            else:
                self.rec.instant("slo_miss", "obs.slo", cls=cls,
                                 uid=req["uid"], ttft_s=req["_ttft"],
                                 target_s=float(target))

    def slo_attainment(self) -> Dict[str, float]:
        """Measured TTFT attainment per SLO class seen so far
        (ok / total; 1.0 before any traffic in a class)."""
        out: Dict[str, float] = {}
        for cls in sorted(self._slo_classes):
            total = self.metrics.counter(
                f"{self.name}.slo.{cls}.total").value
            ok = self.metrics.counter(f"{self.name}.slo.{cls}.ok").value
            out[cls] = (ok / total) if total else 1.0
        return out

    # -- introspection ------------------------------------------------------

    def kv_cache_bytes(self) -> int:
        """Device bytes held by the KV state (pool or dense cache)."""
        if self.kv_mode == "paged":
            return self.kv.nbytes()
        return sum(int(x.nbytes) for x in jax.tree.leaves(self.cache))

    def row_capacity(self) -> int:
        """Max tokens (prompt + generation) one request may ever hold."""
        if self.kv_mode == "paged":
            return self.kv.row_capacity()
        return self.max_seq

    # -- mesh plumbing ------------------------------------------------------

    def _place_state(self) -> None:
        """Commit device placements once at setup: base params and
        adapter slabs fully replicated, KV pools split on the page axis
        into per-device sub-pools. Every jitted step's output carries
        the same shardings, and hot-swap slab writes preserve them — so
        placement is paid once, not per dispatch, and nothing retraces
        when adapters or pages churn."""
        rep = NamedSharding(self.mesh, P())
        self.params = jax.device_put(self.params, rep)
        self.registry.place(rep)
        pool = NamedSharding(self.mesh,
                             shard_rules.page_pool_pspec(self.mesh))
        self.kv.pools = jax.device_put(self.kv.pools, pool)

    def _shard_mapped_steps(self):
        """The three step impls wrapped for the mesh. Row-indexed state
        (tables/idx/tokens/positions/lengths/logits) splits over the
        data axes in the same contiguous row blocks ``PagedKV.shard_of``
        uses; pools split on the page axis; params/slabs replicate.
        Per-row compute touches nothing across rows, so no collectives —
        each device runs the identical single-device step on its block
        (``check_vma=False``: replication inference has no rule for the
        linalg/gather custom calls inside).

        Prefill is the one replicated-compute step: every device runs
        the same (1, C) chunk, but only the owner shard's table stack
        row maps live pages (``PagedKV.prefill_tables``) — the rest
        write their local trash page and produce discarded logits, and
        the host slices the owner's block out of the stacked (S·C, V)
        output."""
        axes = shard_rules.data_shard_axes(self.mesh)

        def row(ndim):
            return P(axes, *((None,) * (ndim - 1)))

        rep = P()
        pool = shard_rules.page_pool_pspec(self.mesh)
        step = self._wrap_decode_shaped(self._paged_step_impl)
        verify = jax.shard_map(
            self._verify_impl, mesh=self.mesh,
            in_specs=(rep, rep, pool, row(2), row(1), row(2), row(1),
                      row(1)),
            out_specs=(row(3), pool), check_vma=False)
        prefill = jax.shard_map(
            self._prefill_impl, mesh=self.mesh,
            in_specs=(rep, rep, pool, row(2), row(1), rep, rep, rep),
            out_specs=(row(2), pool), check_vma=False)
        return step, verify, prefill

    def _wrap_decode_shaped(self, impl):
        """shard_map any decode-step-shaped fn — ``(params, slabs,
        pools, tables, idx, tokens, pos, lens) -> ((B, V) logits,
        pools)`` — over the mesh; identity when unsharded. The engine's
        own decode step and the drafter's shallow draft step both go
        through here, so they shard identically."""
        if self.num_shards <= 1:
            return impl
        axes = shard_rules.data_shard_axes(self.mesh)

        def row(ndim):
            return P(axes, *((None,) * (ndim - 1)))

        rep = P()
        pool = shard_rules.page_pool_pspec(self.mesh)
        return jax.shard_map(
            impl, mesh=self.mesh,
            in_specs=(rep, rep, pool, row(2), row(1), row(2), row(1),
                      row(1)),
            out_specs=(row(2), pool), check_vma=False)

    # -- jitted bodies ------------------------------------------------------

    def _embed(self, params, tokens, pos):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)          # (B,S,d)
        if cfg.rope_theta == 0:
            x = x * math.sqrt(cfg.d_model) + sinusoidal_positions(
                pos, cfg.d_model).astype(x.dtype)
        return x

    def _logits(self, params, x):
        head = params.get("lm_head")
        return x @ (head if head is not None else params["embed"].T)

    def _dense_step_impl(self, params, slabs, cache, idx, tokens, pos):
        """tokens: (B,1) int32, pos: (B,) int32, idx: (B,) int32 slab slots
        -> (logits (B,V), cache)."""
        self.trace_count += 1   # side effect fires at trace time only
        x = self._embed(params, tokens, pos[:, None])

        def scan_body(carry, xs):
            lp, slab_l, lc = xs
            y, new_lc = _layer_decode_dense(carry, lp, slab_l, lc, idx, pos,
                                            self.cfg, self.use_pallas)
            return y, new_lc

        x, new_cache = lax.scan(scan_body, x,
                                (params["layers"], slabs, cache))
        x = norm(x, params["final_norm"])
        return self._logits(params, x[:, 0, :]), new_cache

    def _decode_kv_valid(self, tables, lens):
        """Built once per decode step, outside the layer scan: the
        (shard-local) pool's validity mask for plain attention; None
        under the Pallas kernel, which reads through the tables."""
        if self.use_pallas:
            return None
        return pool_kv_valid(tables, lens, self.kv.pages_per_shard + 1,
                             self.page_size)

    def _paged_step_impl(self, params, slabs, pools, tables, idx, tokens,
                         pos, lens):
        """tokens: (B,1), pos: (B,), lens: (B,) valid tokens incl. this
        one (0 for inactive rows), tables: (B,P) -> (logits, pools)."""
        self.trace_count += 1
        ps = self.page_size
        x = self._embed(params, tokens, pos[:, None])
        page = jnp.take_along_axis(tables, (pos // ps)[:, None], axis=1)[:, 0]
        page = jnp.where(lens > 0, page, self.kv.trash)  # inactive -> trash
        slot = pos % ps
        kv_valid = self._decode_kv_valid(tables, lens)

        def scan_body(carry, xs):
            lp, slab_l, lc = xs
            y, new_lc = _layer_decode_paged(
                carry, lp, slab_l, lc, idx, pos, lens, page, slot, tables,
                kv_valid, self.cfg, self.use_pallas, ps)
            return y, new_lc

        x, new_pools = lax.scan(scan_body, x,
                                (params["layers"], slabs, pools))
        x = norm(x, params["final_norm"])
        return self._logits(params, x[:, 0, :]), new_pools

    def _verify_impl(self, params, slabs, pools, tables, idx, tokens,
                     pos0, nv):
        """Speculative verify: score a window of S = spec_k + 1 tokens
        per row (the context token + spec_k drafts) in one dispatch.
        tokens: (B, S), pos0: (B,) window start (the position the
        context token's KV lands in), nv: (B,) valid tokens in the
        window (0 for inactive rows), tables: (B, P)
        -> (logits (B, S, V), pools). Token i of row b sits at absolute
        position pos0[b] + i; its K/V is written into the row's pages
        first (tail tokens past nv -> trash), then all S positions
        attend causally through the multi-token paged read
        (kernels/verify.py on TPU, the gather oracle elsewhere)."""
        self.trace_count += 1
        ps = self.page_size
        s = tokens.shape[1]
        p = tables.shape[1]
        tpos = pos0[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        x = self._embed(params, tokens, tpos)
        # Write targets: beyond-window positions can step past the page
        # table; clip, then let the nv mask (and the trash entries the
        # allocator leaves in unallocated table slots) steer them away.
        pageidx = jnp.minimum(tpos // ps, p - 1)
        page = jnp.take_along_axis(tables, pageidx, axis=1)
        page = jnp.where(jnp.arange(s)[None, :] < nv[:, None], page,
                         self.kv.trash)
        slot = tpos % ps
        lens = jnp.where(nv > 0, pos0 + nv, 0)

        def scan_body(carry, xs):
            lp, slab_l, lc = xs
            y, new_lc = _layer_verify_paged(
                carry, lp, slab_l, lc, idx, tpos, lens, page, slot,
                tables, pos0, self.cfg, self.use_pallas, ps)
            return y, new_lc

        x, new_pools = lax.scan(scan_body, x,
                                (params["layers"], slabs, pools))
        x = norm(x, params["final_norm"])
        return self._logits(params, x), new_pools

    def _prefill_impl(self, params, slabs, pools, table_row, idx, tokens,
                      pos0, nvalid):
        """One chunk of one row's prompt. table_row: (1,P), idx: (1,),
        tokens: (1,C), pos0/nvalid: traced scalars (chunk offset / valid
        tokens in this chunk) -> (logits (C,V), pools)."""
        self.trace_count += 1
        ps = self.page_size
        c = tokens.shape[1]
        p = table_row.shape[1]
        tpos = pos0 + jnp.arange(c, dtype=jnp.int32)[None, :]    # (1, C)
        x = self._embed(params, tokens, tpos)
        pageidx = jnp.minimum(tpos[0] // ps, p - 1)
        page = jnp.take(table_row[0], pageidx)
        page = jnp.where(jnp.arange(c) < nvalid, page, self.kv.trash)
        slot = tpos[0] % ps

        def scan_body(carry, xs):
            lp, slab_l, lc = xs
            y, new_lc = _layer_prefill_paged(
                carry, lp, slab_l, lc, idx, tpos, page, slot, table_row,
                pos0, self.cfg, self.use_pallas, ps)
            return y, new_lc

        x, new_pools = lax.scan(scan_body, x,
                                (params["layers"], slabs, pools))
        x = norm(x, params["final_norm"])
        return self._logits(params, x[0]), new_pools

    @staticmethod
    def _reset_impl(cache, row_mask):
        """Invalidate the KV prefix of recycled rows (value-only update)."""
        pos = jnp.where(row_mask[None, :, None], -1, cache["pos"])
        return {**cache, "pos": pos}

    # -- scheduler ----------------------------------------------------------

    def submit(self, prompt, adapter_id: str,
               max_new_tokens: int = 16,
               slo_class: Optional[str] = None) -> str:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        total = prompt.size + max_new_tokens
        if total > self.row_capacity():
            what = (f"{self.kv.pages_for(total)} pages" if
                    self.kv_mode == "paged" else f"max_seq {self.max_seq}")
            raise ValueError(
                f"prompt+generation {total} exceeds per-request capacity "
                f"{self.row_capacity()} ({what})")
        if not self.registry.has(adapter_id):
            raise KeyError(f"unknown adapter {adapter_id!r}")
        uid = f"req{self._uid}"
        self._uid += 1
        req = {"uid": uid, "prompt": prompt, "out": [],
               "t": 0, "max_new": int(max_new_tokens),
               "adapter": adapter_id}
        if slo_class is not None:
            req["slo"] = str(slo_class)
        if self.rec.enabled:
            req["_ts"] = self.rec.now()
            extra = {"slo_class": req["slo"]} if "slo" in req else {}
            self.rec.instant("submit", self._track(req),
                             prompt=int(prompt.size),
                             max_new=int(max_new_tokens),
                             adapter=adapter_id, **extra)
        self._queue.append(req)
        return uid

    def _finish(self, row: int, req: dict) -> None:
        self._done[req["uid"]] = np.asarray(req["out"], np.int32)
        self.registry.release(req["adapter"])
        if self.kv_mode == "paged":
            self.kv.release(row)
        self._rows[row] = None
        if self.rec.enabled and "_ts" in req:
            dur = self.rec.now() - req["_ts"]
            self.metrics.histogram(f"{self.name}.request_s").observe(dur)
            if dur > 0:
                self.metrics.histogram(
                    f"{self.name}.request_tok_s").observe(
                    len(req["out"]) / dur)
            self.rec.instant("finish", self._track(req),
                             tokens=len(req["out"]),
                             replays=req.get("_replays", 0))

    def _preempt(self, row: int) -> None:
        """Evict a row: free its pages + adapter pin and replay the
        request from scratch later (greedy decode is deterministic, so
        the re-run reproduces the same tokens)."""
        req = self._rows[row]
        self.registry.release(req["adapter"])
        pages_freed = self.kv.allocated(row)
        self.kv.release(row)
        req.update(t=0, out=[])
        req.pop("slot", None)
        # Replay accounting makes preemption visible outside debug
        # prints: a per-request counter plus a trace instant.
        req["_replays"] = req.get("_replays", 0) + 1
        if self.rec.enabled:
            self.rec.instant("preempt", self._track(req),
                             pages_freed=int(pages_freed))
        self._queue.appendleft(req)
        self._rows[row] = None
        self.preemptions += 1
        self.metrics.counter(
            f"{self.name}.replay_pages").inc(int(pages_freed))

    def _admit(self) -> int:
        admitted = 0
        freed = np.zeros((self.max_batch,), bool)
        any_freed = False
        free_rows = [r for r in range(self.max_batch)
                     if self._rows[r] is None]
        while self._queue and free_rows:
            head = self._queue[0]
            need = 0
            if self.kv_mode == "paged":
                # Page-gated admission: cover the prompt plus the first
                # generated token; later growth extends. A row's pages
                # come from its own shard's sub-pool, so pick the first
                # free row whose shard can cover the head (with one
                # shard this is exactly the old first-free-row scan).
                need = self.kv.pages_for(head["prompt"].size + 1)
                row = next((r for r in free_rows
                            if self.kv.free_count_for(r) >= need), None)
                if row is None:
                    self.deferrals += 1
                    if self.rec.enabled:
                        self.rec.instant("defer", self._track(head),
                                         need_pages=int(need))
                    break   # FCFS: wait for pages, don't starve head
            else:
                row = free_rows[0]
            try:
                slot = self.registry.acquire(head["adapter"])
            except RuntimeError:
                break   # every slab slot pinned: wait for a release
            free_rows.remove(row)
            req = self._queue.popleft()
            req["slot"] = slot
            self._rows[row] = req
            admitted += 1
            if self.rec.enabled:
                self.rec.instant(
                    "replay" if req.get("_replays") else "admit",
                    self._track(req), row=int(row))
            if self.kv_mode == "paged":
                if not self.kv.admit(row, need):   # free_count said yes
                    raise RuntimeError(
                        f"page accounting violated: admission of row "
                        f"{row} failed after the free-count check")
                self._prefill_row(row, req)
            else:
                freed[row] = True
                any_freed = True
        if any_freed:
            self.cache = self._reset(self.cache, jnp.asarray(freed))
        return admitted

    def _prefill_row(self, row: int, req: dict) -> None:
        """Chunked prefill: the whole prompt in ceil(len/chunk) jitted
        dispatches, then the first generated token from the last valid
        logit. The row joins the decode batch already past its prompt."""
        prompt = req["prompt"]
        c = self.prefill_chunk
        # One idx entry per shard (all the same slot: the gather out of
        # the replicated slabs is harmless on non-owner shards).
        idx = jnp.full((self.kv.num_shards,), req["slot"], jnp.int32)
        own = self.kv.shard_of(row)
        logits = None
        nv = 0
        rec = self.rec
        for lo in range(0, prompt.size, c):
            nv = min(c, prompt.size - lo)
            # Fresh buffer every chunk: device_put can alias numpy memory
            # on CPU, and the previous chunk's dispatch may still be
            # reading it asynchronously — mutating in place races.
            toks = np.zeros((1, c), np.int32)
            toks[0, :nv] = prompt[lo:lo + nv]
            with rec.span("serve.prefill_chunk", self._track(req),
                          pos0=int(lo), tokens=int(nv)):
                logits, pools = self._prefill(
                    self.params, self.registry.slabs(), self.kv.pools,
                    self.kv.prefill_tables(row), idx,
                    jnp.asarray(toks), np.int32(lo), np.int32(nv))
            self.kv.pools = pools
            self.prefill_calls += 1
        # Sharded prefill stacks every shard's (C, V) logits; only the
        # owner shard attended live pages — slice its block.
        logits = logits[own * c:own * c + c]
        self.prefill_tokens += int(prompt.size)
        first = int(jnp.argmax(logits[nv - 1]))
        req["t"] = int(prompt.size)
        req["out"] = [first]
        self.tokens_generated += 1
        if rec.enabled:
            self._note_first_token(req)
        if len(req["out"]) >= req["max_new"]:
            self._finish(row, req)

    def _spec_window(self, req: dict) -> int:
        """Draft tokens worth verifying for this row: never more than the
        request could still commit (a dispatch commits 1..k+1 tokens)."""
        return min(self.spec_k, req["max_new"] - len(req["out"]) - 1)

    def _ensure_pages(self, lookahead: Optional[Dict[int, int]] = None
                      ) -> None:
        """Every active row must own the page its next token lands in —
        plus ``lookahead[row]`` further positions for a speculative
        window — extending, and preempting the youngest other rows when
        the pool is dry."""
        lookahead = lookahead or {}
        for row in range(self.max_batch):
            req = self._rows[row]
            if req is None:
                continue
            needed = (req["t"] + lookahead.get(row, 0)) \
                // self.page_size + 1
            if self.kv.allocated(row) >= needed:
                continue
            grow = needed - self.kv.allocated(row)
            if not self.kv.extend(row, grow):
                # Preemption is a shard-local affair: the row's pages can
                # only come from its own sub-pool, so victims do too.
                alloc = self.kv.allocator_for(row)
                alloc.pin(row)
                victims = alloc.victims(grow)
                alloc.unpin(row)
                if victims is None:
                    raise RuntimeError(
                        f"KV pool exhausted: row {row} needs {grow} more "
                        f"page(s) and no unpinned row can be preempted")
                if any(self._rows[int(v)]["t"] >= req["t"]
                       for v in victims):
                    # Never tear down a row that is at least as far
                    # along as the one asking: at exactly-critical
                    # pressure (e.g. two rows in a 5-page sub-pool) the
                    # laggard and leader otherwise preempt each other
                    # forever, neither reaching its final page count.
                    # Re-queueing the laggard keeps the pool's most-
                    # advanced row monotone — a global progress
                    # guarantee, so decode always terminates.
                    self._preempt(row)
                    continue
                for victim in victims:
                    self._preempt(int(victim))
                if not self.kv.extend(row, grow):  # victims covered grow
                    raise RuntimeError(
                        f"page accounting violated: row {row} cannot "
                        f"extend by {grow} page(s) after preemption")
            if self.rec.enabled:
                self.rec.instant("extend", self._track(req),
                                 pages=int(grow))

    def _slot_order(self, idx: np.ndarray, active_mask: np.ndarray):
        """Stable permutation grouping batch rows by adapter slot
        (inactive rows last) — applied to every per-row input of a paged
        dispatch, so rows sharing an adapter sit adjacent for the BGMV
        gather (the precondition for SGMV-style tile reuse). Host-side
        values only: same shapes every step, nothing retraces. Returns
        ``(perm, inv)`` — dispatch inputs take ``x[perm]``, outputs come
        back via ``y[inv]`` — and records the distinct-slot count in
        ``bgmv_groups``."""
        key = np.where(active_mask, idx, np.iinfo(np.int32).max)
        self.bgmv_groups = len(set(idx[active_mask].tolist()))
        if self.kv_mode == "paged" and self.kv.num_shards > 1:
            # Rows must stay on the shard owning their pages: sort
            # within each contiguous shard block, never across.
            rps = self.kv.rows_per_shard
            perm = np.concatenate([
                s * rps + np.argsort(key[s * rps:(s + 1) * rps],
                                     kind="stable")
                for s in range(self.kv.num_shards)])
        else:
            perm = np.argsort(key, kind="stable")
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.size)
        return perm, inv

    def step_batch(self) -> None:
        """Admit (+prefill), page, run one decode (or draft+verify)
        step, harvest/recycle."""
        admitted = self._admit()
        if self.kv_mode == "paged":
            look = None
            if self.drafter is not None:
                look = {i: self._spec_window(r)
                        for i, r in enumerate(self._rows) if r is not None}
            self._ensure_pages(look)
        active = [(i, r) for i, r in enumerate(self._rows) if r is not None]
        if not active:
            # admitted rows may have finished inside _admit (prefill +
            # max_new=1): that is progress, not a stall
            if self._queue and admitted == 0:
                if self.kv_mode == "paged" and \
                        self.kv.max_free_count() < self.kv.pages_for(
                            self._queue[0]["prompt"].size + 1):
                    # no row active yet pages are missing: pinned by
                    # someone outside this engine
                    raise RuntimeError(
                        f"{len(self._queue)} queued requests but the page "
                        f"pool is exhausted and no row is active")
                # no row made progress and none will: every slab slot is
                # pinned by someone outside this engine
                raise RuntimeError(
                    f"{len(self._queue)} queued requests but no adapter "
                    f"slot can be acquired and no row is active")
            return
        if self.drafter is not None:
            self._spec_dispatch(active)
            return
        tokens = np.zeros((self.max_batch, 1), np.int32)
        pos = np.zeros((self.max_batch,), np.int32)
        idx = np.zeros((self.max_batch,), np.int32)
        lens = np.zeros((self.max_batch,), np.int32)
        for i, req in active:
            t = req["t"]
            if self.kv_mode == "dense" and t >= self.max_seq:
                raise RuntimeError(
                    f"row {i} reached position {t} >= max_seq "
                    f"{self.max_seq}: the dense ring would wrap and "
                    f"corrupt attention (writes are dropped instead)")
            tokens[i, 0] = req["prompt"][t] if t < req["prompt"].size \
                else req["out"][-1]
            pos[i] = t
            idx[i] = req["slot"]
            lens[i] = t + 1
        rec = self.rec
        # the argmax harvest inside blocks on the logits, so the span is
        # a true step latency (host + device)
        with rec.span("serve.decode_step", self._engine_track,
                      batch=len(active)) as step:
            if self.kv_mode == "paged":
                perm, inv = self._slot_order(idx, lens > 0)
                logits, self.kv.pools = self._step(
                    self.params, self.registry.slabs(), self.kv.pools,
                    jnp.asarray(self.kv.tables[perm]),
                    jnp.asarray(idx[perm]), jnp.asarray(tokens[perm]),
                    jnp.asarray(pos[perm]), jnp.asarray(lens[perm]))
                nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)[inv]
            else:
                logits, self.cache = self._step(
                    self.params, self.registry.slabs(), self.cache,
                    jnp.asarray(idx), jnp.asarray(tokens), jnp.asarray(pos))
                nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        if rec.enabled:
            self.metrics.histogram(
                f"{self.name}.decode_step_s").observe(step.seconds)
        self.steps += 1
        for i, req in active:
            req["t"] += 1
            if req["t"] >= req["prompt"].size:       # past prefill: sample
                req["out"].append(int(nxt[i]))
                self.tokens_generated += 1
                if rec.enabled:
                    self._note_first_token(req)
            if len(req["out"]) >= req["max_new"]:    # finished: recycle row
                self._finish(i, req)

    def _spec_dispatch(self, active) -> None:
        """One draft–verify round: the drafter proposes up to ``spec_k``
        tokens per row, one verify dispatch scores every draft position
        plus the model's own next token, and each row commits the
        longest matching prefix + 1 (exact greedy token-match, so output
        is guaranteed identical to plain decode). Rejected suffixes roll
        back by truncating the row's page list — KV already written for
        rejected positions dies by the length mask and is overwritten in
        place when decode reaches those positions again."""
        s = self.spec_k + 1
        tokens = np.zeros((self.max_batch, s), np.int32)
        pos0 = np.zeros((self.max_batch,), np.int32)
        idx = np.zeros((self.max_batch,), np.int32)
        nv = np.zeros((self.max_batch,), np.int32)
        props = np.asarray(self.drafter.propose(self, active), np.int32)
        if props.shape != (len(active), self.spec_k):
            raise ValueError(
                f"drafter proposed {props.shape}, expected "
                f"{(len(active), self.spec_k)}")
        for j, (i, req) in enumerate(active):
            # paged rows join the batch past their prompt (prefill runs
            # at admission), so the context token is always a sample
            k_b = self._spec_window(req)
            tokens[i, 0] = req["out"][-1]
            tokens[i, 1:1 + k_b] = props[j, :k_b]
            nv[i] = k_b + 1
            pos0[i] = req["t"]
            idx[i] = req["slot"]
        perm, inv = self._slot_order(idx, nv > 0)
        rec = self.rec
        with rec.span("serve.verify_step", self._engine_track,
                      batch=len(active)) as step:
            logits, self.kv.pools = self._verify(
                self.params, self.registry.slabs(), self.kv.pools,
                jnp.asarray(self.kv.tables[perm]), jnp.asarray(idx[perm]),
                jnp.asarray(tokens[perm]), jnp.asarray(pos0[perm]),
                jnp.asarray(nv[perm]))
            greedy = np.asarray(jnp.argmax(logits, axis=-1), np.int32)[inv]
        if rec.enabled:
            self.metrics.histogram(
                f"{self.name}.decode_step_s").observe(step.seconds)
        self.steps += 1
        self.spec_dispatches += 1
        for i, req in active:
            k_b = int(nv[i]) - 1
            accepted = 0
            while accepted < k_b and \
                    tokens[i, 1 + accepted] == greedy[i, accepted]:
                accepted += 1
            commit = accepted + 1     # matched drafts + the model's own
            req["out"].extend(int(x) for x in greedy[i, :commit])
            req["t"] += commit
            self.tokens_generated += commit
            self.drafted_tokens += k_b
            self.accepted_tokens += accepted
            if len(req["out"]) >= req["max_new"]:
                self._finish(i, req)
            else:
                # rollback: pages past the next write position go home
                self.rollback_pages += self.kv.truncate(i, req["t"])

    def spec_stats(self) -> Dict[str, float]:
        """Speculative-decode introspection (all zeros without a
        drafter)."""
        return {
            "dispatches": self.spec_dispatches,
            "drafted": self.drafted_tokens,
            "accepted": self.accepted_tokens,
            "acceptance_rate": self.accepted_tokens
            / max(self.drafted_tokens, 1),
            "rollback_pages": self.rollback_pages,
        }

    def run(self) -> Dict[str, np.ndarray]:
        """Drive until every submitted request has finished."""
        while self._queue or any(r is not None for r in self._rows):
            self.step_batch()
        out, self._done = self._done, {}
        return out
