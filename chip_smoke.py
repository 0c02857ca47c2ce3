"""Bring-up smoke run of both hot paths on a TPU, at published widths.

    python chip_smoke.py            # one chip: phases `fed` and `serve`
    python chip_smoke.py --chips 4  # four chips: sharded aggregation and
                                    # sharded serving against one device

Phase ``fed`` runs the paper's path on RoBERTa-large (random backbone from
``--seed``, synthetic MRPC pairs): two synchronous HLoRA rounds through
``FedSession`` + ``SyncRound`` (vmapped cohort training, factored-SVD
aggregation, measured wire messages), then one exact-SVD aggregation of
the last cohort with the Pallas ``recon_agg`` reconstruction against the
einsum one. Phase ``serve`` serves 8 requests over 4 heterogeneous-rank
adapters on Gemma-2B in bf16 through ``ServeEngine`` on its Pallas path
(BGMV, paged attention, flash chunked prefill) against the same engine
with ``use_pallas=False``.

``--chips 4`` runs only what exists across chips: ``AggregationEngine``
over a (4, 1) data x model mesh against the same engine on one device
(bit-identical, including a batch that does not divide by 4), and
``ServeEngine(mesh=...)`` against the one-device engine (identical greedy
tokens).

Each phase prints its compile seconds, seconds per steady round or step
and the device's ``peak_bytes_in_use``: bring-up facts, not benchmark
figures. The last line of stdout is one JSON object naming the device.
The run refuses any platform but the TPU; the phase functions themselves
take any config, so tests drive them on the CPU at reduced widths.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.agg_engine import AggregationEngine  # noqa: E402
from repro.fed.client import join_adapters  # noqa: E402
from repro.fed.schedulers import SyncRound  # noqa: E402
from repro.fed.session import FedSession, ServerConfig  # noqa: E402
from repro.fed.simulation import SimConfig, make_experiment_setup  # noqa
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.serve import AdapterRegistry, ServeEngine  # noqa: E402
from repro.serve.oracle import make_demo_adapter  # noqa: E402

# Pallas recon_agg against the einsum reconstruction, both f32: relative
# Frobenius error of the aggregated rank-r_max update.
AGG_REL_TOL = 1e-5
# Serving in bf16: the Pallas and the plain path round differently, and a
# greedy decode that meets a near-tie in the logits continues on another
# token for the rest of that request. Every request's first token must
# agree, and at least this share of all generated tokens.
MIN_TOKEN_AGREEMENT = 0.75
ADAPTER_RANKS = (2, 4, 6, 8)


def _log(phase: str, **facts) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


def _peak_bytes(device=None):
    """The device's ``peak_bytes_in_use``, or None where the backend
    keeps no memory statistics (the CPU)."""
    stats = (device or jax.devices()[0]).memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


def _delta_w(tree):
    """Product A·B per target (client 0, every layer) in full f32: what
    the factors encode, free of the SVD's sign choices."""
    return {t: jnp.einsum("...ir,...ro->...io", ad["A"][0], ad["B"][0],
                          precision=jax.lax.Precision.HIGHEST)
            for t, ad in tree.items()}


def _full_rank(tree):
    """The same adapters with zero-masked rank columns appended up to the
    cohort's summed rank. An exact SVD at that rank truncates nothing, so
    the aggregated A·B is the reconstructed ΔW itself, and a comparison
    of two reconstructions is not scaled up by a small singular-value gap
    at the adapters' own rank."""
    def pad(x, axis, extra):
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, extra)
        return jnp.pad(x, widths)

    out = {}
    for t, ad in tree.items():
        k, r = ad["mask"].shape[0], ad["mask"].shape[-1]
        extra = (k - 1) * r
        out[t] = {"A": pad(ad["A"], -1, extra), "B": pad(ad["B"], -2, extra),
                  "mask": pad(ad["mask"], -1, extra)}
    return out


def _rel_frob(got: dict, want: dict) -> float:
    num = sum(float(jnp.sum((got[t] - want[t]) ** 2)) for t in want)
    den = sum(float(jnp.sum(want[t] ** 2)) for t in want)
    return (num / max(den, 1e-30)) ** 0.5


# ---------------------------------------------------------------------------
# Phase fed
# ---------------------------------------------------------------------------

def fed_phase(cfg, sim: SimConfig, *, rounds: int = 2) -> dict:
    """``rounds`` synchronous HLoRA rounds, then the recon_agg check."""
    scfg = ServerConfig(strategy="hlora", rank_policy="random", r_min=2,
                        r_max=cfg.lora.r_max, num_clients=8,
                        clients_per_round=4, seed=sim.seed)
    base = model_lib.init_params(jax.random.PRNGKey(sim.seed), cfg)
    (session_kw, cohort_train, _, data_fn, _, eval_fn) = \
        make_experiment_setup(cfg, sim, scfg, base_params=base)
    session = FedSession(cfg, scfg, **session_kw)

    last = {}

    def data(cohort, rnd):
        last["cohort"] = np.asarray(cohort)
        return data_fn(cohort, rnd)

    def train(frozen, trainable, masks, batches):
        out, losses = cohort_train(frozen, trainable, masks, batches)
        last["tree"] = join_adapters(out["factors"], masks)
        return out, losses

    history, secs = {}, []
    for _ in range(rounds):
        t0 = time.perf_counter()
        h = SyncRound().run(session, train, data, 1, eval_fn=eval_fn)
        jax.block_until_ready(session.global_lora)
        secs.append(time.perf_counter() - t0)
        for k, v in h.items():
            history.setdefault(k, []).extend(v)

    losses = history["train_loss"]
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite train_loss {losses}")
    if not _finite(session.redistribute(last["cohort"])):
        raise AssertionError("redistributed factors are not finite")
    down, up = history["downlink_bytes"], history["uplink_bytes"]
    if min(down) <= 0 or min(up) <= 0:
        raise AssertionError(f"wire bytes down={down} up={up}")

    # One exact-SVD aggregation of the last cohort: Pallas recon_agg
    # against the einsum, same inputs, same engine otherwise.
    tree = _full_rank(last["tree"])
    eta = session.cohort_weights(last["cohort"])
    full = {t: jnp.ones_like(ad["mask"][:1]) for t, ad in tree.items()}
    kw = dict(strategy="hlora", method="exact", split=scfg.split,
              new_masks=full, key=jax.random.PRNGKey(sim.seed))
    t0 = time.perf_counter()
    got, _ = AggregationEngine(use_pallas=True)(tree, eta, cfg.lora.alpha,
                                                **kw)
    jax.block_until_ready(got)
    agg_s = time.perf_counter() - t0
    want, _ = AggregationEngine(use_pallas=False)(tree, eta, cfg.lora.alpha,
                                                  **kw)
    err = _rel_frob(_delta_w(got), _delta_w(want))
    if not err <= AGG_REL_TOL:
        raise AssertionError(
            f"recon_agg vs einsum rel Frobenius {err} > {AGG_REL_TOL}")

    facts = {
        "model": cfg.name, "rounds": rounds,
        "train_loss": [float(x) for x in losses],
        "eval_acc": [float(x) for x in history["eval_acc"]],
        "downlink_bytes": [int(x) for x in down],
        "uplink_bytes": [int(x) for x in up],
        "compile_s": secs[0] - secs[-1] if rounds > 1 else None,
        "round_s": secs[-1],
        "exact_agg_first_call_s": agg_s,
        "recon_agg_rel_frob": err,
        "peak_bytes_in_use": _peak_bytes(),
    }
    for k, v in facts.items():
        _log("fed", **{k: v})
    return facts


# ---------------------------------------------------------------------------
# Phase serve
# ---------------------------------------------------------------------------

def serve_setup(cfg, *, seed: int, n_requests: int, prompt_len: int,
                dtype):
    """Params in ``dtype`` from ``seed``, 4 demo adapters, prompts."""
    key = jax.random.PRNGKey(seed)
    params = model_lib.init_params(key, cfg, dtype)
    adapters = {f"client{i}": make_demo_adapter(
        jax.random.fold_in(key, 100 + i), cfg, r)
        for i, r in enumerate(ADAPTER_RANKS)}
    prompts = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 3), (n_requests, prompt_len), 3,
        cfg.vocab_size))
    return params, adapters, prompts


def serve_wave(params, cfg, adapters, prompts, new_tokens: int, *,
               use_pallas=None, mesh=None, dtype=jnp.bfloat16,
               waves: int = 2):
    """Serve every prompt (request i on adapter i mod 4) ``waves`` times
    through one engine; returns (tokens of the last wave, seconds per
    wave, engine). The first wave compiles, later ones replay."""
    reg = AdapterRegistry(cfg, capacity=len(adapters))
    for aid, tree in adapters.items():
        reg.register(aid, tree)
    engine = ServeEngine(params, cfg, reg, max_batch=len(prompts),
                         max_seq=prompts.shape[1] + new_tokens,
                         use_pallas=use_pallas, cache_dtype=dtype,
                         mesh=mesh)
    names = sorted(adapters)
    secs, toks = [], None
    for _ in range(waves):
        t0 = time.perf_counter()
        uids = [engine.submit(p, names[i % len(names)],
                              max_new_tokens=new_tokens)
                for i, p in enumerate(prompts)]
        outs = engine.run()
        secs.append(time.perf_counter() - t0)
        toks = np.stack([outs[u] for u in uids])
    return toks, secs, engine


def serve_phase(cfg, *, seed: int = 0, n_requests: int = 8,
                prompt_len: int = 64, new_tokens: int = 16,
                dtype=jnp.bfloat16) -> dict:
    params, adapters, prompts = serve_setup(
        cfg, seed=seed, n_requests=n_requests, prompt_len=prompt_len,
        dtype=dtype)
    got, secs, engine = serve_wave(params, cfg, adapters, prompts,
                                   new_tokens, use_pallas=True, dtype=dtype)
    steps = engine.steps // len(secs)
    want, _, _ = serve_wave(params, cfg, adapters, prompts, new_tokens,
                            use_pallas=False, dtype=dtype, waves=1)
    agree = int(np.sum(got == want))
    first = int(np.sum(got[:, 0] == want[:, 0]))
    facts = {
        "model": cfg.name, "requests": n_requests,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "first_token_agree": f"{first}/{n_requests}",
        "token_agree": f"{agree}/{got.size}",
        "trace_count": engine.trace_count,
        "compile_s": secs[0] - secs[-1],
        "steps_per_wave": steps,
        "step_s": secs[-1] / max(steps, 1),
        "peak_bytes_in_use": _peak_bytes(),
    }
    for k, v in facts.items():
        _log("serve", **{k: v})
    if first != n_requests:
        raise AssertionError(f"first tokens differ: {got[:, 0]} vs "
                             f"{want[:, 0]}")
    if agree < MIN_TOKEN_AGREEMENT * got.size:
        raise AssertionError(f"only {agree}/{got.size} tokens agree")
    return facts


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def cohort_tree(cfg, *, seed: int, layers=None):
    """A RoBERTa-style cohort tree: 4 clients of ranks 2/4/6/8, stacked
    on a leading client axis; ``layers`` keeps only the first few layers
    (a batch of targets x layers that need not divide the mesh)."""
    key = jax.random.PRNGKey(seed)
    clients = [make_demo_adapter(jax.random.fold_in(key, i), cfg, r)
               for i, r in enumerate(ADAPTER_RANKS)]
    tree = jax.tree.map(lambda *xs: jnp.stack(xs), *clients)
    if layers is not None:
        tree = jax.tree.map(lambda x: x[:, :layers], tree)
    return tree


def mesh_agg_phase(cfg, mesh, *, seed: int = 0) -> dict:
    """Sharded aggregation against one device, bit for bit, on the full
    cohort tree and on one whose batch does not divide the mesh."""
    ndev = mesh.shape["data"]
    facts = {}
    for label, layers in (("full", None),
                          ("odd", 3 if cfg.num_layers >= 3 else 1)):
        tree = cohort_tree(cfg, seed=seed, layers=layers)
        batch = len(tree) * next(iter(tree.values()))["A"].shape[1]
        if label == "odd" and batch % ndev == 0:
            raise AssertionError(f"batch {batch} divides {ndev}")
        eta = jnp.arange(1.0, 1.0 + len(ADAPTER_RANKS))
        one, s_one = AggregationEngine()(tree, eta, cfg.lora.alpha)
        t0 = time.perf_counter()
        shd, s_shd = AggregationEngine(mesh=mesh)(tree, eta,
                                                  cfg.lora.alpha)
        jax.block_until_ready(shd)
        secs = time.perf_counter() - t0
        for t in one:
            for leaf in ("A", "B", "mask"):
                np.testing.assert_array_equal(
                    np.asarray(shd[t][leaf]), np.asarray(one[t][leaf]),
                    err_msg=f"{label} {t}/{leaf}")
            np.testing.assert_array_equal(np.asarray(s_shd[t]),
                                          np.asarray(s_one[t]),
                                          err_msg=f"{label} spectrum {t}")
        for t in sorted(shd):
            _log("mesh-agg", tree=label, target=t,
                 sharding=shd[t]["A"].sharding)
        facts[label] = {"batch": batch, "bit_identical": True,
                        "first_call_s": secs}
        _log("mesh-agg", tree=label, batch=batch, bit_identical=True,
             first_call_s=secs)
    return facts


def mesh_serve_phase(cfg, mesh, *, seed: int = 0, n_requests: int = 8,
                     prompt_len: int = 64, new_tokens: int = 16,
                     dtype=jnp.bfloat16) -> dict:
    """Sharded serving against the one-device engine: identical greedy
    tokens, KV pools split over every device of the mesh."""
    params, adapters, prompts = serve_setup(
        cfg, seed=seed, n_requests=n_requests, prompt_len=prompt_len,
        dtype=dtype)
    want, _, one = serve_wave(params, cfg, adapters, prompts, new_tokens,
                              dtype=dtype, waves=1)
    del one
    got, secs, engine = serve_wave(params, cfg, adapters, prompts,
                                   new_tokens, mesh=mesh, dtype=dtype)
    k_pool = jax.tree.leaves(engine.kv.pools)[0]
    wq = engine.params["layers"]["attn"]["wq"]
    _log("mesh-serve", kv_pool_sharding=k_pool.sharding)
    _log("mesh-serve", params_sharding=wq.sharding)
    ndev = mesh.devices.size
    if len(k_pool.sharding.device_set) != ndev:
        raise AssertionError(f"KV pool on {k_pool.sharding.device_set}, "
                             f"not on all {ndev} devices")
    same = int(np.sum(got == want))
    facts = {"token_identical": f"{same}/{got.size}",
             "compile_s": secs[0] - secs[-1], "wave_s": secs[-1],
             "trace_count": engine.trace_count,
             "peak_bytes_in_use": [_peak_bytes(d)
                                   for d in mesh.devices.flat]}
    for k, v in facts.items():
        _log("mesh-serve", **{k: v})
    if same != got.size:
        raise AssertionError(f"sharded tokens differ: {got} vs {want}")
    return facts


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    _log("device", kind=devices[0].device_kind, count=len(devices),
         compile_cache=enable_compile_cache())

    roberta, gemma = get_config("roberta-large"), get_config("gemma-2b")
    if args.chips == 1:
        fed_phase(roberta, SimConfig(task="mrpc", pretrain_steps=0,
                                     seed=args.seed))
        serve_phase(gemma, seed=args.seed)
    else:
        mesh = jax.make_mesh((args.chips, 1), ("data", "model"),
                             devices=devices[:args.chips])
        mesh_agg_phase(roberta, mesh, seed=args.seed)
        mesh_serve_phase(gemma, mesh, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
