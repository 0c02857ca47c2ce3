"""Mesh-native engines: 8-device host-CPU equivalence for the shard_map'd
aggregation and serve hot paths.

``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must be set before
the first jax device query, so everything multi-device here runs in a
child pytest spawned by ``test_mesh_suite_in_subprocess`` (see the
``host_mesh_env`` fixture) and marked by ``REPRO_MESH_CHILD``; in the
parent tier-1 process those tests skip and only the driver and the
device-free ``make_host_mesh`` validation run.

What the child pins, per the mesh-native contract:

* sharded aggregation **bit-identical** to single-device for every
  strategy (engine-level: hlora factored/exact + naive; session-level:
  naive/hlora/flora through ``aggregate_round`` and ``flush_async``) —
  each batch item runs whole on one device, so the op sequence is the
  single-device one exactly;
* sharded ``ServeEngine`` greedy decode **exact** vs the merged-weight
  oracle, including paged preemption pressure, hot-swap, and the
  speculative draft–verify path — with trace counts flat throughout;
* the kernel wrappers' ``batch_align`` padding computed from per-shard
  shapes (odd per-device batches round-trip exactly).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

IN_CHILD = os.environ.get("REPRO_MESH_CHILD") == "1"
child = pytest.mark.skipif(
    not IN_CHILD, reason="needs the 8-device child process (spawned by "
                         "test_mesh_suite_in_subprocess)")

PROMPT_LEN = 6
STEPS = 10
PAGED_TRACES = 2


# ---------------------------------------------------------------------------
# Parent-side: the driver + device-free validation
# ---------------------------------------------------------------------------

@pytest.mark.skipif(IN_CHILD, reason="already inside the mesh child")
def test_mesh_suite_in_subprocess(host_mesh_env):
    """Run this very file under 8 forced host devices in a child pytest;
    every ``child``-marked test below must pass there."""
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x",
         "-p", "no:cacheprovider", os.path.abspath(__file__)],
        env=host_mesh_env, capture_output=True, text=True, timeout=1800)
    tail = (proc.stdout or "") + (proc.stderr or "")
    assert proc.returncode == 0, tail[-4000:]
    assert " passed" in proc.stdout, tail[-4000:]


def test_make_host_mesh_validation():
    """Device-free satellite regressions: axis bounds and the XLA_FLAGS
    hint when the host has too few devices."""
    import jax

    from repro.launch.mesh import data_axis_size, make_host_mesh
    with pytest.raises(ValueError, match="must be >= 1"):
        make_host_mesh(data=0)
    if jax.device_count() < 8:
        with pytest.raises(ValueError, match="xla_force_host_platform"):
            make_host_mesh(data=8)
    assert data_axis_size(None) == 1
    m = make_host_mesh()           # the historical 1x1 mesh still builds
    assert m.shape["data"] == 1 and m.shape["model"] == 1
    assert data_axis_size(m) == 1


# ---------------------------------------------------------------------------
# Child-side fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_setup():
    import jax

    from repro.configs import get_reduced
    from repro.models import model as model_lib
    from repro.serve.oracle import make_demo_adapter

    cfg = get_reduced("gemma-2b")
    key = jax.random.PRNGKey(0)
    params = model_lib.init_params(key, cfg)
    ranks = (2, 4, 6, 8)
    adapters = {
        f"client{i}": make_demo_adapter(jax.random.fold_in(key, 100 + i),
                                        cfg, r)
        for i, r in enumerate(ranks)}
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 3), (8, PROMPT_LEN), 3, cfg.vocab_size))
    return cfg, params, adapters, prompts


def _registry(cfg, adapters):
    from repro.serve import AdapterRegistry
    reg = AdapterRegistry(cfg, capacity=len(adapters))
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    return reg


def _rand_adapters(key, k, layers, d_in, r, d_out, targets=("q", "v")):
    import jax
    import jax.numpy as jnp
    out = {}
    for j, t in enumerate(targets):
        ks = jax.random.split(jax.random.fold_in(key, j), 3)
        out[t] = {
            "A": jax.random.normal(ks[0], (k, layers, d_in, r),
                                   jnp.float32),
            "B": jax.random.normal(ks[1], (k, layers, r, d_out),
                                   jnp.float32),
            "mask": (jax.random.uniform(ks[2], (k, layers, r)) > 0.3
                     ).astype(jnp.float32),
        }
    return out


# ---------------------------------------------------------------------------
# Child-side: aggregation equivalence
# ---------------------------------------------------------------------------

@child
@pytest.mark.parametrize("strategy,method,split", [
    ("hlora", "factored", "paper"),
    ("hlora", "exact", "sqrt"),
    ("naive", "factored", "paper"),
])
def test_agg_engine_sharded_bit_identical(strategy, method, split):
    """The 8-way sharded engine returns bit-identical factors and
    spectra to the single-device engine — including the tile-padded
    odd batch (2 targets x 3 layers = 6 items over 8 devices)."""
    import jax
    import jax.numpy as jnp

    from repro.core.agg_engine import AggregationEngine
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=8)
    adapters = _rand_adapters(jax.random.PRNGKey(0), 4, 3, 16, 4, 12)
    eta = jnp.arange(1.0, 5.0)
    e1 = AggregationEngine(factored_impl="qr")
    e8 = AggregationEngine(factored_impl="qr", mesh=mesh)
    o1, s1 = e1(adapters, eta, 8.0, strategy=strategy, method=method,
                split=split)
    o8, s8 = e8(adapters, eta, 8.0, strategy=strategy, method=method,
                split=split)
    for t in o1:
        for leaf in ("A", "B", "mask"):
            np.testing.assert_array_equal(np.asarray(o1[t][leaf]),
                                          np.asarray(o8[t][leaf]),
                                          err_msg=f"{t}/{leaf}")
        np.testing.assert_array_equal(np.asarray(s1[t]),
                                      np.asarray(s8[t]), err_msg=t)


@child
def test_agg_engine_sharded_trace_flat():
    """Round 2 replays the compiled executable on the mesh too."""
    import jax
    import jax.numpy as jnp

    from repro.core.agg_engine import AggregationEngine
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=8)
    adapters = _rand_adapters(jax.random.PRNGKey(1), 4, 3, 16, 4, 12)
    eta = jnp.ones((4,))
    e8 = AggregationEngine(mesh=mesh)
    e8(adapters, eta, 8.0)
    traces = e8.trace_count
    e8(adapters, eta, 8.0)
    assert e8.trace_count == traces


@child
@pytest.mark.parametrize("strategy", ["naive", "hlora", "flora"])
def test_fedsession_mesh_matches_single_device(strategy):
    """FedSession(mesh=...) is the one choke point: a sync round under
    every strategy lands on the same global adapter as the unsharded
    session (<= 1e-6 rel)."""
    import jax

    from repro.configs import get_reduced
    from repro.fed.session import FedSession, ServerConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as model_lib

    cfg = get_reduced("roberta-large")
    base = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_host_mesh(data=8)
    scfg = ServerConfig(num_clients=4, clients_per_round=4,
                        strategy=strategy, rank_policy="uniform", seed=0)
    sess_1 = FedSession(cfg, scfg, base)
    sess_m = FedSession(cfg, scfg, base, mesh=mesh)
    assert sess_m.engine.mesh is mesh
    cohort = np.arange(4)
    key = jax.random.PRNGKey(7)
    stacked = sess_1.redistribute(cohort)
    for i, t in enumerate(stacked):
        stacked[t]["B"] = jax.random.normal(
            jax.random.fold_in(key, i), stacked[t]["B"].shape) \
            * stacked[t]["mask"][..., :, None]
    sess_1.aggregate_round(stacked, cohort)
    sess_m.aggregate_round(stacked, cohort)
    for t in sess_1.global_lora:
        for leaf in ("A", "B"):
            np.testing.assert_allclose(
                np.asarray(sess_m.global_lora[t][leaf]),
                np.asarray(sess_1.global_lora[t][leaf]),
                rtol=1e-6, atol=1e-7, err_msg=f"{strategy}/{t}/{leaf}")


@child
def test_fedsession_mesh_async_flush_matches():
    """The async merge path goes through the same engine choke point:
    flush_async on the mesh session == flush_async unsharded."""
    import jax

    from repro.configs import get_reduced
    from repro.fed.session import AsyncConfig, FedSession, ServerConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as model_lib

    cfg = get_reduced("roberta-large")
    base = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_host_mesh(data=8)
    scfg = ServerConfig(num_clients=3, clients_per_round=3,
                        strategy="hlora", rank_policy="uniform", seed=0)
    acfg = AsyncConfig(base_weight=0.5)
    sess_1 = FedSession(cfg, scfg, base, acfg=acfg)
    sess_m = FedSession(cfg, scfg, base, acfg=acfg, mesh=mesh)
    cohort = np.arange(3)
    key = jax.random.PRNGKey(9)
    stacked = sess_1.redistribute(cohort)
    trained = {t: dict(ad) for t, ad in stacked.items()}
    for i, t in enumerate(trained):
        trained[t]["B"] = jax.random.normal(
            jax.random.fold_in(key, i), trained[t]["B"].shape) \
            * trained[t]["mask"][..., :, None]
    for sess in (sess_1, sess_m):
        updates = [sess.make_update(
            int(cid),
            {t: {leaf: ad[leaf][i] for leaf in ("A", "B", "mask")}
             for t, ad in trained.items()},
            start_version=0)
            for i, cid in enumerate(cohort)]
        assert sess.flush_async(updates) == [True] * 3
    for t in sess_1.global_lora:
        for leaf in ("A", "B"):
            np.testing.assert_allclose(
                np.asarray(sess_m.global_lora[t][leaf]),
                np.asarray(sess_1.global_lora[t][leaf]),
                rtol=1e-6, atol=1e-7, err_msg=f"{t}/{leaf}")


# ---------------------------------------------------------------------------
# Child-side: sharded serving
# ---------------------------------------------------------------------------

@child
def test_sharded_serve_exact_vs_oracle(serve_setup):
    """8 request rows over 8 devices (one per shard), heterogeneous-rank
    adapters: greedy tokens identical to the merged-weight oracle, trace
    count flat at prefill + decode."""
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine
    from repro.serve.oracle import merged_greedy

    cfg, params, adapters, prompts = serve_setup
    mesh = make_host_mesh(data=8)
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=8, max_seq=PROMPT_LEN + STEPS,
                         mesh=mesh)
    assert engine.kv.num_shards == 8
    uids = [engine.submit(prompts[i], f"client{i % 4}",
                          max_new_tokens=STEPS) for i in range(8)]
    outs = engine.run()
    assert engine.trace_count == PAGED_TRACES
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % 4}"], STEPS)
        np.testing.assert_array_equal(outs[uid], want)


@child
def test_sharded_serve_preemption_exact(serve_setup):
    """Per-shard page pools under pressure (2 rows per shard contending
    for 5 pages): admission defers / extension preempts inside the row's
    own shard, outputs stay oracle-exact, traces stay flat, and every
    sub-pool conserves its pages."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine
    from repro.serve.oracle import merged_greedy

    cfg, params, adapters, prompts = serve_setup
    mesh = make_host_mesh(data=4)
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=8, max_seq=PROMPT_LEN + STEPS,
                         page_size=4, num_pages=20, prefill_chunk=4,
                         mesh=mesh)
    assert engine.kv.num_shards == 4
    assert engine.kv.pages_per_shard == 5
    # decode reads each shard's sub-pool (5 pages + its trash) in place
    assert engine.decode_kv_slots == 4 * (5 + 1) * 4
    uids = [engine.submit(prompts[i], f"client{i % 4}",
                          max_new_tokens=STEPS) for i in range(8)]
    outs = engine.run()
    assert engine.deferrals + engine.preemptions > 0   # real pressure
    assert engine.trace_count == PAGED_TRACES
    for alloc in engine.kv.allocators:
        alloc.check()
        assert alloc.free_count == engine.kv.pages_per_shard
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i % 4}"], STEPS)
        np.testing.assert_array_equal(outs[uid], want)


@child
def test_sharded_hot_swap_no_retrace(serve_setup):
    """Hot-swap on the mesh: slabs are replicated via NamedSharding, the
    refresh is a value-only slab write that keeps the placement — zero
    recompilation, and the swap takes effect exactly."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine
    from repro.serve.oracle import merged_greedy

    cfg, params, adapters, prompts = serve_setup
    mesh = make_host_mesh(data=2)
    reg = _registry(cfg, adapters)
    engine = ServeEngine(params, cfg, reg, max_batch=2,
                         max_seq=PROMPT_LEN + STEPS, mesh=mesh)
    uid = engine.submit(prompts[0], "client3", max_new_tokens=STEPS)
    before = engine.run()[uid]
    traces = engine.trace_count

    swapped = {t: dict(ad, B=ad["B"] + 0.05) for t, ad
               in adapters["client3"].items()}
    reg.register("client3", swapped)
    reg.refresh("client3")
    uid2 = engine.submit(prompts[0], "client3", max_new_tokens=STEPS)
    after = engine.run()[uid2]

    assert engine.trace_count == traces          # zero recompilation
    want = merged_greedy(params, cfg, prompts[0], swapped, STEPS)
    np.testing.assert_array_equal(after, want)
    assert not np.array_equal(before, after)


@child
def test_sharded_spec_decode_lossless(serve_setup):
    """Draft–verify over the mesh (SelfDrafter's step shard_maps through
    the same wrapper as decode): output identical to plain sharded
    decode, traces flat after binding."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine
    from repro.serve.oracle import merged_greedy
    from repro.serve.spec import SelfDrafter

    cfg, params, adapters, prompts = serve_setup
    mesh = make_host_mesh(data=4)
    engine = ServeEngine(params, cfg, _registry(cfg, adapters),
                         max_batch=4, max_seq=PROMPT_LEN + STEPS,
                         drafter=SelfDrafter(draft_layers=1), spec_k=3,
                         mesh=mesh)
    uids = [engine.submit(prompts[i], f"client{i}", max_new_tokens=STEPS)
            for i in range(4)]
    outs = engine.run()
    traces = engine.trace_count
    assert engine.spec_dispatches > 0
    for i, uid in enumerate(uids):
        want = merged_greedy(params, cfg, prompts[i],
                             adapters[f"client{i}"], STEPS)
        np.testing.assert_array_equal(outs[uid], want)
    # a second wave replays every compiled step
    for i in range(4):
        engine.submit(prompts[i], f"client{i}", max_new_tokens=4)
    engine.run()
    assert engine.trace_count == traces


# ---------------------------------------------------------------------------
# Child-side: per-shard kernel-wrapper padding
# ---------------------------------------------------------------------------

@child
def test_bgmv_batch_align_per_shard_odd_batch():
    """shard_map'd bgmv with an odd per-device batch (3 rows/device on a
    4-way mesh): batch_align pads each shard's remainder locally and the
    result round-trips exactly to the unsharded call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=4)
    key = jax.random.PRNGKey(0)
    b, s, d_in, r, d_out = 12, 3, 8, 4, 16     # 3 rows per device
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, d_in))
    a = jax.random.normal(ks[1], (s, d_in, r))
    bb = jax.random.normal(ks[2], (s, r, d_out))
    idx = jax.random.randint(ks[3], (b,), 0, s).astype(jnp.int32)

    want = ops.bgmv(x, a, bb, idx)

    fn = jax.shard_map(
        lambda x_, i_: ops.bgmv(x_, a, bb, i_, batch_align=4),
        mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=P("data"), check_vma=False)
    got = jax.jit(fn)(x, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@child
def test_paged_attention_batch_align_odd_batch():
    """batch_align on an odd row count is a pure round-trip: padded rows
    read at length 0 and are sliced off."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    key = jax.random.PRNGKey(1)
    b, h, hkv, dh, np_, ps, p = 5, 4, 2, 8, 6, 4, 3
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, dh))
    k_pool = jax.random.normal(ks[1], (np_ + 1, ps, hkv, dh))
    v_pool = jax.random.normal(ks[2], (np_ + 1, ps, hkv, dh))
    tables = jnp.asarray(np.random.default_rng(0).integers(
        0, np_, (b, p)), jnp.int32)
    lengths = jnp.asarray([1, 5, 9, 12, 3], jnp.int32)
    base = ops.paged_attention(q, k_pool, v_pool, tables, lengths,
                               page_size=ps)
    aligned = ops.paged_attention(q, k_pool, v_pool, tables, lengths,
                                  page_size=ps, batch_align=8)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(aligned))


# ---------------------------------------------------------------------------
# Child-side: chip_smoke.py's four-chip phases at reduced widths
# ---------------------------------------------------------------------------

@child
def test_chip_smoke_mesh_phases():
    """``chip_smoke.py --chips 4`` runs these two phases on a (4, 1)
    mesh: sharded aggregation bit-identical to one device (full tree and
    a batch that does not divide the mesh), sharded serving token-
    identical to one device with the KV pools on every device."""
    import chip_smoke
    from repro.configs import get_reduced
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=4)
    agg = chip_smoke.mesh_agg_phase(get_reduced("roberta-large"), mesh)
    assert agg["odd"]["batch"] % 4 and agg["full"]["bit_identical"]
    serve = chip_smoke.mesh_serve_phase(get_reduced("gemma-2b"), mesh,
                                        prompt_len=8, new_tokens=4)
    assert serve["token_identical"] == "32/32"
