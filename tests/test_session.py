"""Unified FedSession API: golden equivalence vs the pre-refactor loop,
wire-format round-trips, scheduler policies, and checkpoint/resume.

The golden test keeps a *verbatim replica* of the pre-refactor
``FedServer`` + ``run_experiment`` orchestration (the seed string-dispatch
path) and requires the session-driven ``run_experiment`` to reproduce its
history bit-for-bit at fixed seed — the refactor must be an evaluation
strategy, not a semantic change.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_reduced
from repro.core import agg_engine, lora
from repro.fed import (AsyncConfig, AsyncFedServer, BufferedAsync,
                       FedSession, FLoRAStacking, SemiSync, ServerConfig,
                       SimConfig, SyncRound, run_experiment)
from repro.fed import messages as msg_lib
from repro.fed.session import assign_ranks
from repro.fed.simulation import make_experiment_setup, pretrain_backbone
from repro.models import transformer as tf_lib

ALPHA_SIM = SimConfig(task="mrpc", num_examples=512, eval_examples=128,
                      rounds=3, local_steps=2, local_batch=8,
                      pretrain_steps=20, lr=1e-3, seed=0)


@pytest.fixture(scope="module")
def cfg():
    return get_reduced("roberta-large")


@pytest.fixture(scope="module")
def base(cfg):
    return pretrain_backbone(cfg, ALPHA_SIM)


# ---------------------------------------------------------------------------
# Pre-refactor replica (seed orchestration, kept verbatim as the oracle)
# ---------------------------------------------------------------------------

class _LegacyFedServer:
    """The pre-refactor FedServer, verbatim (string dispatch, hlora-only
    scale gating, out-of-session head averaging order)."""

    def __init__(self, cfg, scfg, base_params, client_sizes):
        from repro.fed.client import split_head
        self.cfg, self.scfg = cfg, scfg
        frozen, head = split_head(base_params)
        self.base, self.global_head = frozen, head
        self.rng = np.random.default_rng(scfg.seed)
        self.client_sizes = np.asarray(client_sizes, np.int64)
        self.ranks = assign_ranks(scfg, self.client_sizes, None, self.rng)
        self.global_lora = tf_lib.init_lora(
            jax.random.PRNGKey(scfg.seed), cfg)
        self.engine = agg_engine.default_engine()

    def sample_cohort(self):
        return self.rng.choice(self.scfg.num_clients,
                               size=self.scfg.clients_per_round,
                               replace=False)

    def cohort_adapters(self, cohort):
        k, r_max = len(cohort), self.cfg.lora.r_max
        out = {}
        for t, ad in self.global_lora.items():
            masks = np.zeros((k, *ad["mask"].shape), np.float32)
            for i, cid in enumerate(cohort):
                masks[i, ...] = (np.arange(r_max)
                                 < int(self.ranks[cid])).astype(np.float32)
            m = jnp.asarray(masks)
            a = jnp.broadcast_to(ad["A"][None], (k, *ad["A"].shape)) \
                * m[..., None, :]
            b = jnp.broadcast_to(ad["B"][None], (k, *ad["B"].shape)) \
                * m[..., :, None]
            if self.scfg.strategy == "hlora":
                r_eff = jnp.maximum(jnp.sum(m, axis=-1), 1.0)
                b = b * (r_eff / float(r_max))[..., None, None]
            out[t] = {"A": a, "B": b, "mask": m}
        return out

    def cohort_weights(self, cohort):
        n_k = self.client_sizes[cohort].astype(np.float64)
        return jnp.asarray(n_k / n_k.sum(), jnp.float32)

    def cohort_heads(self, cohort):
        k = len(cohort)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (k, *x.shape)),
            self.global_head)

    def update_global(self, stacked, cohort, stacked_heads=None):
        eta = self.cohort_weights(cohort)
        if stacked_heads:
            self.global_head = jax.tree.map(
                lambda x: jnp.tensordot(eta, x.astype(jnp.float32),
                                        axes=1).astype(x.dtype),
                stacked_heads)
        full = {t: jnp.ones_like(ad["mask"][:1])
                for t, ad in stacked.items()}
        out, _ = self.engine(
            stacked, eta, self.cfg.lora.alpha,
            strategy=self.scfg.strategy, method=self.scfg.svd_method,
            split=self.scfg.split, new_masks=full,
            key=jax.random.PRNGKey(int(self.rng.integers(2 ** 31))))
        self.global_lora = {
            t: {"A": ad["A"][0], "B": ad["B"][0], "mask": ad["mask"][0]}
            for t, ad in out.items()}


def _legacy_run_experiment(cfg, sim, scfg, base_params):
    """The pre-refactor run_experiment loop, verbatim."""
    from repro.data import dirichlet_partition, make_pair_classification
    from repro.fed.client import (join_adapters, make_cohort_train,
                                  split_adapters, split_head)
    from repro.fed.simulation import _stack_client_data
    from repro.models import model as model_lib
    from repro.optim import adamw

    frozen, _ = split_head(base_params)
    tokens, labels = make_pair_classification(
        sim.task, sim.num_examples, seed=sim.seed, vocab_size=cfg.vocab_size)
    ev_tokens, ev_labels = make_pair_classification(
        sim.task, sim.eval_examples, seed=sim.seed + 10_000,
        vocab_size=cfg.vocab_size)
    ev_batch = {"tokens": jnp.asarray(ev_tokens),
                "labels": jnp.asarray(ev_labels)}
    shards = dirichlet_partition(labels, scfg.num_clients,
                                 sim.dirichlet_alpha, seed=sim.seed)
    server = _LegacyFedServer(cfg, scfg, base_params,
                              client_sizes=[len(s) for s in shards])
    cohort_train = make_cohort_train(cfg, adamw(sim.lr))

    # the backbone is an argument, as in make_experiment_setup: a closed-
    # over one is folded in as a constant and rounds differently
    @jax.jit
    def eval_fn(frozen, lora_tree, head):
        params = {**frozen, **head, "lora": lora_tree}
        _, m = model_lib.loss_fn(params, ev_batch, cfg, remat=False)
        return m

    history = {"round": [], "train_loss": [], "eval_acc": [],
               "eval_loss": []}
    for rnd in range(sim.rounds):
        cohort = server.sample_cohort()
        stacked = server.cohort_adapters(cohort)
        factors, masks = split_adapters(stacked)
        trainable = {"factors": factors,
                     "head": server.cohort_heads(cohort)}
        data = _stack_client_data(tokens, labels, shards, cohort, sim, rnd)
        trainable, losses = cohort_train(frozen, trainable, masks, data)
        server.update_global(join_adapters(trainable["factors"], masks),
                             cohort, stacked_heads=trainable["head"])
        history["round"].append(rnd)
        history["train_loss"].append(float(jnp.mean(losses)))
        m = eval_fn(frozen, server.global_lora, server.global_head)
        history["eval_acc"].append(float(m["acc"]))
        history["eval_loss"].append(float(m["loss"]))
    return history


def test_sync_hlora_session_golden_vs_prerefactor(cfg, base):
    """Acceptance gate: SyncRound + HLoRA through the session (wire
    messages and all) reproduces the pre-refactor history BIT-FOR-BIT."""
    scfg = ServerConfig(num_clients=8, clients_per_round=4,
                        strategy="hlora", rank_policy="random",
                        r_min=2, r_max=8, seed=0)
    legacy = _legacy_run_experiment(cfg, ALPHA_SIM, scfg, base)
    got = run_experiment(cfg, ALPHA_SIM, scfg, base_params=base)
    for k in ("round", "train_loss", "eval_acc", "eval_loss"):
        assert got[k] == legacy[k], (k, got[k], legacy[k])
    # wire accounting came along for free — and it is measured, not 0
    assert all(b > 0 for b in got["downlink_bytes"])
    assert all(b > 0 for b in got["uplink_bytes"])


def test_sync_naive_session_golden_vs_prerefactor(cfg, base):
    scfg = ServerConfig(num_clients=8, clients_per_round=4,
                        strategy="naive", rank_policy="random",
                        r_min=2, r_max=8, seed=1)
    legacy = _legacy_run_experiment(cfg, ALPHA_SIM, scfg, base)
    got = run_experiment(cfg, ALPHA_SIM, scfg, base_params=base)
    for k in ("round", "train_loss", "eval_acc", "eval_loss"):
        assert got[k] == legacy[k], k


# ---------------------------------------------------------------------------
# Wire format: serialize -> deserialize round-trips exactly, bytes measured
# ---------------------------------------------------------------------------

def _payload(seed, layers, d_in, d_out, r, dtype):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((layers, d_in, r)).astype(np.float32)
    b = rng.standard_normal((layers, r, d_out)).astype(np.float32)
    if dtype == "bf16":
        a = np.asarray(jnp.asarray(a, jnp.bfloat16))
        b = np.asarray(jnp.asarray(b, jnp.bfloat16))
    return {"q": {"A": a, "B": b}}


@settings(max_examples=12, deadline=None)
@given(r=st.integers(1, 8), layers=st.integers(1, 3),
       dtype=st.sampled_from(["f32", "bf16"]),
       kind=st.sampled_from(["broadcast", "update"]))
def test_wire_roundtrip_exact_and_bytes_measured(r, layers, dtype, kind):
    adapter = _payload(r * 7 + layers, layers, 6, 5, r, dtype)
    head = {"cls_head": np.arange(12, dtype=np.float32).reshape(4, 3)}
    if kind == "broadcast":
        msg = msg_lib.Broadcast(version=3, client_id=7, adapter=adapter,
                                head=head)
        back = msg_lib.Broadcast.from_bytes(msg.to_bytes())
        assert back.version == 3 and back.client_id == 7
    else:
        msg = msg_lib.ClientUpdate(client_id=7, start_version=3,
                                   num_examples=64, adapter=adapter,
                                   head=head)
        back = msg_lib.ClientUpdate.from_bytes(msg.to_bytes())
        assert back.start_version == 3 and back.num_examples == 64
    for t in adapter:
        for leaf in ("A", "B"):
            got, want = back.adapter[t][leaf], adapter[t][leaf]
            assert got.dtype == want.dtype
            assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(back.head["cls_head"], head["cls_head"])
    # reported bytes ARE the buffer size, and the payload dominates it
    raw = msg.to_bytes()
    assert msg.num_bytes == len(raw) == back.num_bytes
    assert msg_lib.payload_bytes(msg) < len(raw) \
        <= msg_lib.payload_bytes(msg) + 2048
    # unpack pads back to r_max with exact zeros + a correct mask
    tree, _ = back.unpack(8)
    assert tree["q"]["A"].shape[-1] == 8
    assert float(jnp.sum(tree["q"]["mask"][0])) == r
    np.testing.assert_array_equal(
        np.asarray(tree["q"]["A"][..., :r]), np.asarray(adapter["q"]["A"]))
    assert not np.any(np.asarray(tree["q"]["A"][..., r:]))


def test_downlink_bytes_rank_truncated(cfg, base):
    """A rank-2 client's broadcast measures ~r/r_max of a rank-8 one."""
    scfg = ServerConfig(num_clients=2, clients_per_round=2,
                        strategy="hlora", rank_policy="uniform", seed=0)
    sess = FedSession(cfg, scfg, base, client_sizes=[64, 64])
    sess.ranks = np.array([2, 8], np.int32)
    stacked = sess.redistribute(np.array([0, 1]))
    sizes = []
    for i in (0, 1):
        sl = {t: {"A": ad["A"][i], "B": ad["B"][i]}
              for t, ad in stacked.items()}
        sizes.append(sess.make_broadcast(i, sl).num_bytes)
    head_b = sum(np.asarray(v).nbytes for v in sess.global_head.values())
    assert sizes[0] < sizes[1]
    # adapter payload scales ∝ r exactly (head + header are rank-free)
    assert (sizes[0] - head_b) < 0.3 * (sizes[1] - head_b)


def _bits_equal(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert (got.dtype, got.shape) == (want.dtype, want.shape), what
    assert got.tobytes() == want.tobytes(), what


def _unpack(msg, r_max):
    """A message's client-side view at r_max, padded here with ``np.pad``
    and the mask rebuilt from the payload's rank, apart from the code
    under test."""
    tree = {}
    for t, ad in msg.adapter.items():
        a, b = np.asarray(ad["A"]), np.asarray(ad["B"])
        r = a.shape[-1]
        tree[t] = {
            "A": np.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, r_max - r)]),
            "B": np.pad(b, [(0, 0)] * (b.ndim - 2)
                        + [(0, r_max - r), (0, 0)]),
            "mask": np.broadcast_to(
                (np.arange(r_max) < r).astype(np.float32),
                (*a.shape[:-2], r_max))}
    return tree, {n: np.asarray(v) for n, v in msg.head.items()}


def _reference_restack(msgs, r_max):
    """The per-client wire path, one message at a time: to_bytes ->
    from_bytes -> unpack, then a stack over the clients; and the summed
    measured bytes."""
    trees, heads, nbytes = [], [], 0
    for m in msgs:
        back = type(m).from_bytes(m.to_bytes())
        nbytes += back.num_bytes
        tree, head = _unpack(back, r_max)
        trees.append(tree)
        heads.append(head)
    out = {t: {leaf: np.stack([c[t][leaf] for c in trees])
               for leaf in ("A", "B", "mask")} for t in trees[0]}
    hs = {n: np.stack([h[n] for h in heads]) for n in heads[0]}
    return out, hs, nbytes


def _mask_ranks(tree, i, r_max):
    return {t: int(np.asarray(ad["mask"][i]).reshape(-1, r_max)[0].sum())
            for t, ad in tree.items()}


def _client_payload(tree, i):
    return {t: {"A": np.asarray(ad["A"][i]), "B": np.asarray(ad["B"][i])}
            for t, ad in tree.items()}


@pytest.mark.parametrize("caps", [None, {"q": 3, "v": 5}],
                         ids=["uncapped", "capped"])
@pytest.mark.parametrize("codec", ["none", "bf16", "int8", "topk"])
def test_cohort_wire_matches_per_client_reference(cfg, base, codec, caps):
    """A round's batched broadcast and collect equal, bit for bit, a
    reference that round-trips each client's message alone, and log the
    sum of those messages' measured bytes."""
    from repro.fed import compress as compress_lib
    scfg = ServerConfig(num_clients=6, clients_per_round=4,
                        strategy="hlora", rank_policy="random", r_min=2,
                        r_max=8, seed=0, codec=codec)
    sess = FedSession(cfg, scfg, base, client_sizes=np.arange(1, 7) * 10)
    sess.target_ranks = caps
    key = jax.random.PRNGKey(11)
    sess.global_lora = {
        t: {**ad, "B": jax.random.normal(jax.random.fold_in(key, j),
                                         ad["B"].shape)}
        for j, (t, ad) in enumerate(sess.global_lora.items())}
    r_max, wire_codec = cfg.lora.r_max, compress_lib.from_name(codec)
    cohort = np.array([4, 0, 5, 2])

    red = sess.redistribute(cohort)
    head = {n: np.asarray(v) for n, v in sess.global_head.items()}
    want, want_heads, want_bytes = _reference_restack(
        [msg_lib.Broadcast(version=sess.version, client_id=int(cid),
                           adapter=msg_lib.truncate_adapter(
                               _client_payload(red, i),
                               _mask_ranks(red, i, r_max)),
                           head=head, codec=wire_codec)
         for i, cid in enumerate(cohort)], r_max)
    tree, heads = sess.broadcast_cohort(cohort)
    for t in want:
        for leaf in ("A", "B", "mask"):
            _bits_equal(tree[t][leaf], want[t][leaf], f"down {t}.{leaf}")
    assert set(heads) == set(want_heads) and heads
    for n in want_heads:
        _bits_equal(heads[n], want_heads[n], f"down head {n}")
    assert sess.comm_log["downlink"][-1] == want_bytes

    # a trained stack: steps only in each client's unmasked directions
    trained = {}
    for j, (t, ad) in enumerate(tree.items()):
        ka, kb = jax.random.split(jax.random.fold_in(key, 100 + j))
        m = ad["mask"]
        trained[t] = {
            "A": ad["A"] + 0.01 * jax.random.normal(ka, ad["A"].shape)
            * m[..., None, :],
            "B": ad["B"] + 0.01 * jax.random.normal(kb, ad["B"].shape)
            * m[..., :, None],
            "mask": m}
    trained_heads = {n: v + 0.5 for n, v in heads.items()}
    want, want_heads, want_bytes = _reference_restack(
        [msg_lib.ClientUpdate(
            client_id=int(cid), start_version=sess.version,
            num_examples=int(sess.client_sizes[cid]),
            adapter=msg_lib.truncate_adapter(
                _client_payload(trained, i),
                _mask_ranks(trained, i, r_max)),
            head={n: np.asarray(v[i]) for n, v in trained_heads.items()},
            codec=wire_codec)
         for i, cid in enumerate(cohort)], r_max)
    got, got_heads = sess.collect_updates(cohort, trained, trained_heads)
    for t in want:
        for leaf in ("A", "B", "mask"):
            _bits_equal(got[t][leaf], want[t][leaf], f"up {t}.{leaf}")
    for n in want_heads:
        _bits_equal(got_heads[n], want_heads[n], f"up head {n}")
    assert sess.comm_log["uplink"][-1] == want_bytes
    assert sess.comm_log["downlink"][-1] > 0 and want_bytes > 0


def test_wire_transfers_do_not_grow_with_the_cohort(cfg, base, monkeypatch):
    """A round's broadcast + collect move the cohort between device and
    host in a fixed number of transfer calls, at 2 clients as at 6, and
    ``fed.wire_transfers`` counts exactly those calls."""
    calls = []
    real_get, real_put = jax.device_get, jax.device_put

    def get(x):
        calls.append("get")
        return real_get(x)

    def put(x, *a, **kw):
        calls.append("put")
        return real_put(x, *a, **kw)
    monkeypatch.setattr(jax, "device_get", get)
    monkeypatch.setattr(jax, "device_put", put)
    per_round = {}
    for k in (2, 6):
        scfg = ServerConfig(num_clients=8, clients_per_round=k,
                            strategy="hlora", rank_policy="random", seed=0)
        sess = FedSession(cfg, scfg, base)
        cohort = sess.sample_cohort()
        counter = sess.metrics.counter("fed.wire_transfers")
        calls.clear()
        stacked, heads = sess.broadcast_cohort(cohort)
        tree, up_heads = sess.collect_updates(cohort, stacked, heads)
        assert len(cohort) == k and len(tree["q"]["A"]) == k
        assert counter.value == len(calls)
        per_round[k] = counter.value
    assert per_round[2] == per_round[6] == 4


# ---------------------------------------------------------------------------
# Satellite: async redistribution gated on strategy (seed bug: hlora scale
# applied under naive), via the one shared redistribution path
# ---------------------------------------------------------------------------

def test_async_adapter_for_gates_scale_on_strategy(cfg, base):
    key = jax.random.PRNGKey(3)
    got = {}
    for strat in ("naive", "hlora"):
        scfg = ServerConfig(num_clients=2, clients_per_round=2,
                            strategy=strat, rank_policy="uniform", seed=0)
        server = AsyncFedServer(cfg, scfg, AsyncConfig(), base, [1.0, 1.0])
        server.ranks = np.array([4, 8], np.int32)
        for i, t in enumerate(server.global_lora):
            server.global_lora[t]["B"] = jax.random.normal(
                jax.random.fold_in(key, i),
                server.global_lora[t]["B"].shape)
        ad, _ = server.adapter_for(0)
        got[strat] = ad
        for t, a in ad.items():
            r_eff = np.asarray(a["mask"]).reshape(-1, 8)[0].sum()
            assert r_eff == 4
            expect = np.asarray(server.global_lora[t]["B"])[..., :4, :]
            scale = 0.5 if strat == "hlora" else 1.0   # 4/8 only for hlora
            np.testing.assert_allclose(
                np.asarray(a["B"])[..., :4, :], expect * scale,
                rtol=1e-6, atol=1e-7, err_msg=(strat, t))
            assert not np.any(np.asarray(a["B"])[..., 4:, :])


# ---------------------------------------------------------------------------
# Satellite: task head folded into the session merge with staleness weights
# ---------------------------------------------------------------------------

def test_async_zero_staleness_head_matches_sync_average(cfg, base):
    """base_weight=1 + zero staleness must degenerate to the plain sync
    FedAvg — head AND adapter (legacy EMA'd the head 0.9/0.1 outside the
    server, ignoring staleness and data weights entirely)."""
    key = jax.random.PRNGKey(9)
    sizes = [32, 64, 96]
    scfg = ServerConfig(num_clients=3, clients_per_round=3,
                        strategy="hlora", rank_policy="uniform", seed=0)
    sess_a = FedSession(cfg, scfg, base, client_sizes=sizes,
                        acfg=AsyncConfig(base_weight=1.0))
    sess_s = FedSession(cfg, scfg, base, client_sizes=sizes)
    cohort = np.array([0, 1, 2])

    stacked = sess_s.redistribute(cohort)
    trained = {t: dict(ad) for t, ad in stacked.items()}
    for i, t in enumerate(trained):
        trained[t]["B"] = jax.random.normal(
            jax.random.fold_in(key, i), trained[t]["B"].shape) \
            * trained[t]["mask"][..., :, None]
    heads = {k: jax.random.normal(jax.random.fold_in(key, 50 + i),
                                  (3, *v.shape))
             for i, (k, v) in enumerate(sess_s.global_head.items())}

    updates = [sess_a.make_update(
        cid, {t: {leaf: ad[leaf][i] for leaf in ("A", "B", "mask")}
              for t, ad in trained.items()},
        start_version=0, head={k: v[i] for k, v in heads.items()})
        for i, cid in enumerate(cohort)]
    flags = sess_a.flush_async(updates)
    assert flags == [True, True, True]

    sess_s.aggregate_round(trained, cohort, stacked_heads=heads)
    for k in sess_s.global_head:
        np.testing.assert_allclose(np.asarray(sess_a.global_head[k]),
                                   np.asarray(sess_s.global_head[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    for t in sess_s.global_lora:
        dw_a = lora.delta_w(sess_a.global_lora[t], cfg.lora.alpha)
        dw_s = lora.delta_w(sess_s.global_lora[t], cfg.lora.alpha)
        np.testing.assert_allclose(np.asarray(dw_a), np.asarray(dw_s),
                                   rtol=1e-4, atol=1e-5, err_msg=t)


# ---------------------------------------------------------------------------
# BufferedAsync: K=1 == event-by-event submit; one engine call per flush
# ---------------------------------------------------------------------------

class _CountingEngine(agg_engine.AggregationEngine):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.calls = 0

    def __call__(self, *a, **kw):
        self.calls += 1
        return super().__call__(*a, **kw)


def _async_setup(cfg, base, sim, scfg):
    (session_kwargs, _cohort, local_train, _data, client_data_fn,
     _eval) = make_experiment_setup(cfg, sim, scfg, base)
    return session_kwargs, local_train, client_data_fn


def test_buffered_async_k1_matches_event_submit(cfg, base):
    """The scheduler's buffered path at K=1 must equal the direct
    AsyncFedServer.submit event loop bit-for-bit on one event stream."""
    import heapq
    scfg = ServerConfig(num_clients=4, clients_per_round=4,
                        rank_policy="random", r_min=2, r_max=8, seed=0)
    sim = SimConfig(**{**ALPHA_SIM.__dict__, "local_steps": 2})
    speeds = np.array([2.0, 1.0, 0.5, 0.25])
    acfg = AsyncConfig(max_staleness=50)
    n_events = 8

    kw1, local_train, data1 = _async_setup(cfg, base, sim, scfg)
    server = AsyncFedServer(cfg, scfg, acfg, base, speeds,
                            client_sizes=kw1["client_sizes"])
    from repro.fed.client import join_adapters, split_adapters
    heap, pending = [], {}
    for cid in range(4):
        ad, ver = server.adapter_for(cid)
        pending[cid] = ad
        heapq.heappush(heap, (1.0 / speeds[cid], cid, ver))
    for _ in range(n_events):
        t_now, cid, ver = heapq.heappop(heap)
        factors, masks = split_adapters(pending[cid])
        trainable = {"factors": factors, "head": server.global_head}
        trained, _ = local_train(server.base, trainable, masks, data1(cid))
        server.submit(cid, join_adapters(trained["factors"], masks), ver,
                      head=trained["head"])
        ad, ver = server.adapter_for(cid)
        pending[cid] = ad
        heapq.heappush(heap, (t_now + 1.0 / speeds[cid], cid, ver))

    kw2, local_train2, data2 = _async_setup(cfg, base, sim, scfg)
    sess = FedSession(cfg, scfg, base, client_sizes=kw2["client_sizes"],
                      acfg=acfg)
    h = BufferedAsync(speeds=speeds, buffer_size=1, acfg=acfg).run(
        sess, local_train2, data2, num_events=n_events)

    assert sess.staleness_log == server.staleness_log
    assert sess.version == server.version
    assert h["flush_events"] == [1] * n_events
    for t in server.global_lora:
        for leaf in ("A", "B", "mask"):
            np.testing.assert_array_equal(
                np.asarray(sess.global_lora[t][leaf]),
                np.asarray(server.global_lora[t][leaf]), err_msg=(t, leaf))
    for k in server.global_head:
        np.testing.assert_array_equal(np.asarray(sess.global_head[k]),
                                      np.asarray(server.global_head[k]))


def test_buffered_flush_is_one_engine_call(cfg, base):
    scfg = ServerConfig(num_clients=4, clients_per_round=4,
                        rank_policy="uniform", seed=0)
    sim = SimConfig(**{**ALPHA_SIM.__dict__, "local_steps": 1})
    kw, local_train, data_fn = _async_setup(cfg, base, sim, scfg)
    eng = _CountingEngine(use_pallas=False)
    sess = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"],
                      engine=eng)
    h = BufferedAsync(speeds=np.ones(4), buffer_size=4,
                      acfg=AsyncConfig()).run(
        sess, local_train, data_fn, num_events=8)
    # 8 events, K=4 -> exactly 2 flushes -> exactly 2 engine calls
    assert h["flush_events"] == [4, 4]
    assert eng.calls == 2
    assert sess.version == 8


def test_async_spectrum_and_per_target_adaptation(cfg, base):
    """Seed gap: the async path supported neither spectrum nor per-target
    rank adaptation. Through the session both work in async flushes."""
    scfg = ServerConfig(num_clients=4, clients_per_round=4,
                        strategy="hlora", rank_policy="spectrum",
                        per_target_ranks=True, r_min=2, r_max=8, seed=0)
    sess = FedSession(cfg, scfg, base, client_sizes=[64] * 4)
    assert (sess.ranks == 8).all()
    key = jax.random.PRNGKey(11)
    ad, ver = sess.adapter_for(0)
    trained = {t: dict(a) for t, a in ad.items()}
    for i, t in enumerate(trained):   # plant a rank-2 signal
        b = trained[t]["B"]
        u = jax.random.normal(jax.random.fold_in(key, i),
                              (*b.shape[:-2], 2, b.shape[-1]))
        trained[t]["B"] = jnp.concatenate(
            [u, jnp.zeros((*b.shape[:-2], b.shape[-2] - 2, b.shape[-1]))],
            axis=-2) * trained[t]["mask"][..., :, None]
    flags = sess.flush_async([sess.make_update(0, trained, ver)])
    assert flags == [True]
    assert sess.last_spectrum is not None
    assert sess.ranks.max() <= 7          # tightened from r_max
    assert sess.target_ranks is not None
    ad2, _ = sess.adapter_for(1)
    for t, cap in sess.target_ranks.items():
        r_eff = int(np.asarray(ad2[t]["mask"]).reshape(-1, 8)[0].sum())
        assert r_eff == min(int(sess.ranks[1]), cap), (t, r_eff)


# ---------------------------------------------------------------------------
# SemiSync
# ---------------------------------------------------------------------------

def test_semisync_infinite_deadline_matches_sync(cfg, base):
    scfg = ServerConfig(num_clients=8, clients_per_round=4,
                        strategy="hlora", rank_policy="random", seed=0)
    h_sync = run_experiment(cfg, ALPHA_SIM, scfg, base_params=base)
    h_semi = run_experiment(
        cfg, ALPHA_SIM, scfg, base_params=base,
        scheduler=SemiSync(speeds=np.ones(8), deadline=1e9))
    for k in ("round", "train_loss", "eval_acc", "eval_loss"):
        assert h_sync[k] == h_semi[k], k
    assert h_semi["stragglers"] == [0] * ALPHA_SIM.rounds


def test_semisync_deadline_cuts_stragglers(cfg, base):
    scfg = ServerConfig(num_clients=8, clients_per_round=4,
                        strategy="hlora", rank_policy="random", seed=0)
    speeds = np.array([4.0] * 6 + [0.1, 0.1])   # two chronic stragglers
    h = run_experiment(cfg, ALPHA_SIM, scfg, base_params=base,
                       scheduler=SemiSync(speeds=speeds, deadline=1.0))
    assert sum(h["stragglers"]) > 0
    assert all(np.isfinite(h["train_loss"]))
    assert all(t <= 1.0 for t in h["round_time"])
    # stragglers never uplink: their bytes are missing from the round
    rounds_with = [i for i, s in enumerate(h["stragglers"]) if s > 0]
    rounds_without = [i for i, s in enumerate(h["stragglers"]) if s == 0]
    if rounds_with and rounds_without:
        assert min(h["uplink_bytes"][i] for i in rounds_without) > \
            min(h["uplink_bytes"][i] for i in rounds_with)


# ---------------------------------------------------------------------------
# FLoRA stacking baseline (one-class strategy addition)
# ---------------------------------------------------------------------------

def test_flora_aggregation_exact_no_scale_broadcast(cfg, base):
    """FLoRA: noise-free stacked aggregation (== exact FedAvg of the
    effective updates, like hlora) but plain truncated redistribution
    (no r/r_max correction, 'sqrt' split)."""
    scfg = ServerConfig(num_clients=6, clients_per_round=3,
                        strategy="flora", rank_policy="uniform", seed=0)
    sess = FedSession(cfg, scfg, base, client_sizes=np.arange(1, 7) * 10)
    assert isinstance(sess.strategy, FLoRAStacking)
    cohort = np.array([1, 2, 5])
    stacked = sess.redistribute(cohort)
    key = jax.random.PRNGKey(3)
    for i, t in enumerate(stacked):
        stacked[t]["B"] = jax.random.normal(
            jax.random.fold_in(key, i), stacked[t]["B"].shape) \
            * stacked[t]["mask"][..., :, None]
    from repro.core.aggregate import reconstruct_global_update
    eta = sess.cohort_weights(cohort)
    sess.aggregate_round(stacked, cohort)
    for t, ad in sess.global_lora.items():
        exact = reconstruct_global_update(stacked[t], eta, cfg.lora.alpha)
        got = lora.delta_w(ad, cfg.lora.alpha)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                                   rtol=1e-3, atol=1e-4, err_msg=t)
    # broadcast: plain truncation of the new global, no scale correction
    sess.ranks = np.array([4] * 6, np.int32)
    out = sess.redistribute(np.array([0]))
    for t, ad in out.items():
        expect = np.asarray(sess.global_lora[t]["B"])[..., :4, :]
        np.testing.assert_array_equal(
            np.asarray(ad["B"][0])[..., :4, :], expect, err_msg=t)


def test_flora_runs_e2e(cfg, base):
    sim = SimConfig(**{**ALPHA_SIM.__dict__, "rounds": 2})
    scfg = ServerConfig(num_clients=8, clients_per_round=4,
                        strategy="flora", rank_policy="random", seed=0)
    h = run_experiment(cfg, sim, scfg, base_params=base)
    assert np.isfinite(h["train_loss"]).all()
    assert np.isfinite(h["eval_acc"]).all()


# ---------------------------------------------------------------------------
# Checkpoint / resume
# ---------------------------------------------------------------------------

def test_session_checkpoint_resume_bitwise(cfg, base, tmp_path):
    scfg = ServerConfig(num_clients=8, clients_per_round=4,
                        strategy="hlora", rank_policy="spectrum",
                        per_target_ranks=True, r_min=2, r_max=8, seed=0)
    (kw, cohort_train, _local, data_fn, _cdata,
     eval_fn) = make_experiment_setup(cfg, ALPHA_SIM, scfg, base)

    sess_full = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"])
    h_full = SyncRound().run(sess_full, cohort_train, data_fn, 4,
                             eval_fn=eval_fn)

    sess_a = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"])
    h_a = SyncRound().run(sess_a, cohort_train, data_fn, 2, eval_fn=eval_fn)
    ckpt = str(tmp_path / "fed")
    sess_a.save(ckpt)
    sess_b = FedSession.restore(ckpt, cfg, scfg, base,
                                client_sizes=kw["client_sizes"])
    assert sess_b.rounds_done == 2
    assert np.array_equal(sess_b.ranks, sess_a.ranks)
    assert sess_b.target_ranks == sess_a.target_ranks
    h_b = SyncRound().run(sess_b, cohort_train, data_fn, 2, eval_fn=eval_fn)

    for k in ("round", "train_loss", "eval_acc", "eval_loss"):
        assert h_a[k] + h_b[k] == h_full[k], k
    for t in sess_full.global_lora:
        for leaf in ("A", "B", "mask"):
            np.testing.assert_array_equal(
                np.asarray(sess_b.global_lora[t][leaf]),
                np.asarray(sess_full.global_lora[t][leaf]),
                err_msg=(t, leaf))


def test_restore_reapplies_saved_strategy(cfg, base, tmp_path):
    """A session saved under 'flora' must not silently resume under
    scfg.strategy's math; an explicit strategy kwarg still wins."""
    scfg = ServerConfig(num_clients=2, clients_per_round=2,
                        strategy="hlora", seed=0)
    sess = FedSession(cfg, scfg, base, client_sizes=[32, 32],
                      strategy="flora")
    d = str(tmp_path / "ck")
    sess.save(d)
    back = FedSession.restore(d, cfg, scfg, base, client_sizes=[32, 32])
    assert isinstance(back.strategy, FLoRAStacking)
    forced = FedSession.restore(d, cfg, scfg, base, client_sizes=[32, 32],
                                strategy="naive")
    assert forced.strategy.name == "naive"


def test_buffered_async_acfg_scoped_to_run(cfg, base):
    """A scheduler without an explicit AsyncConfig must not clobber the
    session's staleness policy; an explicit one applies only inside the
    run and the session's own policy is restored afterwards."""
    scfg = ServerConfig(num_clients=2, clients_per_round=2, seed=0)
    sim = SimConfig(**{**ALPHA_SIM.__dict__, "local_steps": 1})
    kw, local_train, data_fn = _async_setup(cfg, base, sim, scfg)
    speeds = np.array([2.0, 1.0])
    sess = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"],
                      acfg=AsyncConfig(max_staleness=2, base_weight=0.5))
    h = BufferedAsync(speeds=speeds, buffer_size=1).run(
        sess, local_train, data_fn, num_events=3)
    assert sess.acfg.max_staleness == 2 and sess.acfg.base_weight == 0.5
    assert all(h["accepted"])                 # tau <= 2 throughout
    assert all(b > 0 for b in h["uplink_bytes"])   # wire columns surfaced
    sess2 = FedSession(cfg, scfg, base, client_sizes=kw["client_sizes"],
                       acfg=AsyncConfig(max_staleness=2))
    h2 = BufferedAsync(speeds=speeds, buffer_size=1,
                       acfg=AsyncConfig(max_staleness=0)).run(
        sess2, local_train, data_fn, num_events=3)
    assert not all(h2["accepted"])            # override used during run
    assert sess2.acfg.max_staleness == 2      # ...and restored after it


def test_restored_session_spectrum_fallback(cfg, base, tmp_path):
    """A restored session has no engine spectrum: adapt_ranks must run on
    the split-normalized factor-norm fallback of _target_spectra — and
    pick the same per-target ranks under both splits."""
    s_by_target = {"q": np.array([8.0, 4.0] + [1e-3] * 6),
                   "v": np.array([5.0, 4.0, 3.0, 2.0] + [1e-3] * 4)}
    picked = {}
    for split in ("paper", "sqrt"):
        scfg = ServerConfig(num_clients=6, clients_per_round=3,
                            strategy="hlora", rank_policy="spectrum",
                            per_target_ranks=True, split=split,
                            r_min=2, r_max=8, seed=0)
        sess = FedSession(cfg, scfg, base, client_sizes=np.full(6, 32))
        for t, ad in sess.global_lora.items():
            s = s_by_target[t]
            rows = s if split == "paper" else np.sqrt(s)
            b = np.zeros(np.asarray(ad["B"]).shape, np.float32)
            b[..., 0] = rows
            sess.global_lora[t]["B"] = jnp.asarray(b)
        ckpt = str(tmp_path / f"fed_{split}")
        sess.save(ckpt)
        restored = FedSession.restore(ckpt, cfg, scfg, base,
                                      client_sizes=np.full(6, 32))
        assert restored.last_spectrum is None      # fallback territory
        restored.adapt_ranks()
        picked[split] = dict(restored.target_ranks)
    assert picked["paper"] == picked["sqrt"], picked
    assert picked["paper"]["q"] == 2 and picked["paper"]["v"] == 4


# ---------------------------------------------------------------------------
# Mid-flight async checkpoint: save inside a BufferedAsync run, resume
# bit-identically (heap order, pending adapters, K-buffer contents)
# ---------------------------------------------------------------------------

def test_buffered_async_midflight_resume_bitwise(cfg, base, tmp_path):
    """A split async run (4 events -> save -> restore -> 3 events) must
    equal one uninterrupted 7-event run bit-for-bit — including the
    partial K-buffer crossing the checkpoint. ``drain=False`` is what
    makes the split well-defined: the run boundary flushes nothing."""
    from repro.data import make_pair_classification
    from repro.data.partition import client_batches, iid_partition

    scfg = ServerConfig(num_clients=4, clients_per_round=4,
                        strategy="hlora", rank_policy="random",
                        r_min=2, r_max=8, seed=0)
    sim = SimConfig(**{**ALPHA_SIM.__dict__, "local_steps": 2})
    _kw, local_train, _stateful = _async_setup(cfg, base, sim, scfg)
    # a *stateless* data_fn (the stock client_data_fn draws from a shared
    # call-order rng, which a resumed run cannot replay)
    tokens, labels = make_pair_classification(
        "mrpc", 256, seed=0, vocab_size=cfg.vocab_size)
    shards = iid_partition(256, 4, seed=0)
    sizes = [len(s) for s in shards]

    def data_fn(cid):
        return client_batches(tokens, labels, shards[cid], sim.local_steps,
                              sim.local_batch, seed=777 + cid)

    speeds = np.array([2.0, 1.0, 0.5, 0.25])
    acfg = AsyncConfig(max_staleness=50)

    def sched():
        return BufferedAsync(speeds=speeds, buffer_size=3, acfg=acfg,
                             drain=False)

    sess_full = FedSession(cfg, scfg, base, client_sizes=sizes)
    sched().run(sess_full, local_train, data_fn, num_events=7)

    sess_a = FedSession(cfg, scfg, base, client_sizes=sizes)
    sched().run(sess_a, local_train, data_fn, num_events=4)
    # events 1-3 flushed; event 4 is live in the buffer at the split
    assert sess_a.version == 3
    assert len(sess_a.async_state["buffer"]) == 1
    ckpt = str(tmp_path / "async")
    sess_a.save(ckpt)

    sess_b = FedSession.restore(ckpt, cfg, scfg, base, client_sizes=sizes)
    st = sess_b.async_state
    assert st is not None
    assert st["heap"] == sess_a.async_state["heap"]
    assert sorted(st["pending"]) == [0, 1, 2, 3]
    assert len(st["buffer"]) == 1
    # the buffered update survived the checkpoint byte-exactly
    assert st["buffer"][0].to_bytes() == \
        sess_a.async_state["buffer"][0].to_bytes()
    sched().run(sess_b, local_train, data_fn, num_events=3)

    assert sess_b.version == sess_full.version == 6
    assert sess_b.staleness_log == sess_full.staleness_log
    assert sess_b.async_state["heap"] == sess_full.async_state["heap"]
    assert sess_b.async_state["buffer"][0].to_bytes() == \
        sess_full.async_state["buffer"][0].to_bytes()
    # wire accounting lines up event-for-event across the split
    assert sess_b.comm_log["uplink"] == sess_full.comm_log["uplink"]
    assert sess_b.comm_log["downlink"] == sess_full.comm_log["downlink"]
    for t in sess_full.global_lora:
        for leaf in ("A", "B", "mask"):
            np.testing.assert_array_equal(
                np.asarray(sess_b.global_lora[t][leaf]),
                np.asarray(sess_full.global_lora[t][leaf]),
                err_msg=(t, leaf))
    for k in sess_full.global_head:
        np.testing.assert_array_equal(np.asarray(sess_b.global_head[k]),
                                      np.asarray(sess_full.global_head[k]))


# ---------------------------------------------------------------------------
# Deprecated front doors: warn once at construction, behave identically
# ---------------------------------------------------------------------------

def test_fedserver_shim_warns_and_matches_session(cfg, base):
    from repro.fed import FedServer
    scfg = ServerConfig(num_clients=4, clients_per_round=2,
                        strategy="hlora", rank_policy="random",
                        r_min=2, r_max=8, seed=0)
    with pytest.warns(DeprecationWarning,
                      match="FedSession with a SyncRound"):
        srv = FedServer(cfg, scfg, base, client_sizes=[32] * 4)
    sess = FedSession(cfg, scfg, base, client_sizes=[32] * 4)
    np.testing.assert_array_equal(srv.sample_cohort(), sess.sample_cohort())
    cohort = np.array([0, 2])
    stacked = sess.redistribute(cohort)
    legacy = srv.cohort_adapters(cohort)
    key = jax.random.PRNGKey(5)
    for i, t in enumerate(stacked):
        for leaf in ("A", "B", "mask"):
            np.testing.assert_array_equal(np.asarray(legacy[t][leaf]),
                                          np.asarray(stacked[t][leaf]),
                                          err_msg=(t, leaf))
        b = jax.random.normal(jax.random.fold_in(key, i),
                              stacked[t]["B"].shape) \
            * stacked[t]["mask"][..., :, None]
        stacked[t] = dict(stacked[t], B=b)
        legacy[t] = dict(legacy[t], B=b)
    srv.update_global(legacy, cohort)
    sess.aggregate_round(stacked, cohort)
    for t in sess.global_lora:
        for leaf in ("A", "B", "mask"):
            np.testing.assert_array_equal(
                np.asarray(srv.global_lora[t][leaf]),
                np.asarray(sess.global_lora[t][leaf]), err_msg=(t, leaf))


def test_async_fedserver_shim_warns_and_matches_flush(cfg, base):
    import types
    scfg = ServerConfig(num_clients=2, clients_per_round=2,
                        strategy="naive", rank_policy="uniform", seed=0)
    with pytest.warns(DeprecationWarning, match="BufferedAsync"):
        srv = AsyncFedServer(cfg, scfg, AsyncConfig(), base, [1.0, 1.0],
                             client_sizes=[32, 32])
    np.testing.assert_array_equal(srv.sizes, srv.client_sizes)  # legacy name
    sess = FedSession(cfg, scfg, base, client_sizes=[32, 32],
                      acfg=AsyncConfig())
    ad, ver = srv.adapter_for(0)
    key = jax.random.PRNGKey(8)
    trained = {t: dict(a, B=jax.random.normal(
        jax.random.fold_in(key, i), a["B"].shape)
        * a["mask"][..., :, None]) for i, (t, a) in enumerate(ad.items())}
    assert srv.submit(0, trained, ver) is True
    flags = sess.flush_async([types.SimpleNamespace(
        client_id=0, start_version=ver, num_examples=32,
        adapter=trained, head=None)])
    assert flags == [True]
    assert srv.version == sess.version == 1
    for t in sess.global_lora:
        for leaf in ("A", "B", "mask"):
            np.testing.assert_array_equal(
                np.asarray(srv.global_lora[t][leaf]),
                np.asarray(sess.global_lora[t][leaf]), err_msg=(t, leaf))
