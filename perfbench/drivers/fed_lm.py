"""Driver for federated instruction tuning of a causal language model:
synchronous HLoRA rounds through the program's ``FedSession`` +
``SyncRound`` with the vmapped cohort trainer, factored aggregation and
the wire round trip, as ``fed.py`` runs them for the encoder cells.

The traffic is instruction data: each client holds examples of a few
categories (a Dirichlet split of the categories' counts); a local step
trains on one sequence holding one example, a prompt then a response
that copies noised spans of it, right-padded, with the loss on the
response only. Token ids come from a per-category Zipf unigram over the
configuration's vocabulary (its slice).

Set-up, the window and the check follow ``fed.py``: the first
``check_rounds`` rounds warm every program and are followed by the
configuration's plain reference once the window has closed. A traced run
also reads the device time of the program's ``moe.*`` and ``ssm.ssd``
scopes and of its grouped matmuls (``scopes.py``), and the routing
counters the session keeps (``fed.moe_*``).
"""
from __future__ import annotations

import functools
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import bench  # noqa: E402
import fed  # noqa: E402
import flops_hybrid  # noqa: E402

PAD, SEP, EOS, FIRST_WORD = 0, 1, 2, 3
SCOPES = ("moe.route", "moe.experts", "ssm.ssd")
GMM_OP = "ragged-dot-none"


# ---------------------------------------------------------------------------
# Traffic: clients, category mixes, instruction sequences
# ---------------------------------------------------------------------------

def partition(tr: dict, seed: int):
    """Client sizes and category mixes: a Dirichlet(alpha) split of each
    category's examples over the clients; a client under the floor is
    topped up in its largest category."""
    rng = bench.np_rng(seed, "partition")
    k = int(tr["clients"])
    cats = list(tr["categories"].values())
    counts = np.zeros((k, len(cats)), np.int64)
    for c, nc in enumerate(cats):
        props = rng.dirichlet([tr["dirichlet_alpha"]] * k)
        counts[:, c] = np.floor(props * nc).astype(np.int64)
    short = np.maximum(int(tr["min_examples"]) - counts.sum(1), 0)
    counts[np.arange(k), counts.argmax(1)] += short
    sizes = counts.sum(1)
    return sizes, counts / sizes[:, None]


def unigrams(tr: dict, seed: int, vocab: int) -> np.ndarray:
    """Per category, the CDF of a Zipf(s) unigram over the word ids in a
    permutation of its own."""
    ids = np.arange(FIRST_WORD, vocab)
    w = 1.0 / np.arange(1, ids.size + 1) ** float(tr["zipf_s"])
    out = np.empty((len(tr["categories"]), vocab), np.float64)
    for c in range(out.shape[0]):
        p = np.zeros(vocab)
        p[bench.np_rng(seed, "vocab", c).permutation(ids)] = w
        out[c] = np.cumsum(p / p.sum())
    return out


def _lognormal_len(rng, spec: dict) -> int:
    return max(1, int(round(rng.lognormal(np.log(spec["median"]),
                                          spec["sigma"]))))


def example(rng, tr: dict, cdf: np.ndarray):
    """(tokens (S,), labels (S,), real length, predicted positions): a
    prompt, SEP, a response and EOS, right-padded; labels hold the next
    token where it is part of the response (EOS included), else -1."""
    s = int(tr["seq_len"])
    p_len = min(_lognormal_len(rng, tr["prompt_len"]), s - 3)
    r_len = min(_lognormal_len(rng, tr["response_len"]), s - 2 - p_len)

    def draw(n):
        return np.minimum(np.searchsorted(cdf, rng.random(n)),
                          cdf.size - 1)
    prompt = draw(p_len)
    lo, hi = tr["copy_span"]
    resp: List[int] = []
    while len(resp) < r_len:
        n = int(rng.integers(lo, hi + 1))
        if rng.random() < tr["copy_prob"]:
            a = int(rng.integers(0, p_len))
            resp.extend(prompt[a:a + n])
        else:
            resp.extend(draw(n))
    resp = np.asarray(resp[:r_len])
    resp = np.where(rng.random(r_len) < tr["noise"], draw(r_len), resp)
    seq = np.concatenate([prompt, [SEP], resp, [EOS]]).astype(np.int32)
    n = seq.size
    toks = np.full(s, PAD, np.int32)
    toks[:n] = seq
    labels = np.full(s, -1, np.int32)
    labels[p_len:n - 1] = seq[p_len + 1:]
    return toks, labels, n, n - 1 - p_len


def client_batches(seed: int, rnd: int, cid: int, tr: dict,
                   mix: np.ndarray, cdfs: np.ndarray):
    """(steps, batch, seq) tokens and labels, and (steps * batch) pairs
    of (real length, predicted positions), fresh for every (round,
    client): each row's category drawn from the client's mix."""
    rng = bench.np_rng(seed, "rows", rnd, cid)
    st, b = int(tr["local_steps"]), int(tr["local_batch"])
    rows = [example(rng, tr, cdfs[rng.choice(mix.size, p=mix)])
            for _ in range(st * b)]
    toks = np.stack([r[0] for r in rows]).reshape(st, b, -1)
    labels = np.stack([r[1] for r in rows]).reshape(st, b, -1)
    return toks, labels, [(r[2], r[3]) for r in rows]


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def model_config(c: dict):
    from repro.configs.base import LoRAConfig, ModelConfig
    L = c["num_hidden_layers"]
    return ModelConfig(
        name=c["name"], arch_type="hybrid_moe", num_layers=L,
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["shared_intermediate_size"], vocab_size=c["vocab_size"],
        num_experts=c["experts_routed"],
        experts_per_token=c["num_experts_per_tok"],
        moe_d_ff=c["intermediate_size"], moe_shared=True,
        moe_experts_held=c["num_local_experts"],
        moe_expert_offset=c["experts_offset"],
        ssm_state=c["mamba_d_state"], ssm_expand=c["mamba_expand"],
        ssm_head_dim=c["mamba_d_head"], ssm_conv_width=c["mamba_d_conv"],
        ssm_chunk=c["mamba_chunk_size"],
        layer_types=tuple(c["layer_types"][:L]), rope_theta=0.0,
        attention_multiplier=c["attention_multiplier"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=c["residual_multiplier"],
        logits_scaling=float(c["logits_scaling"]),
        norm_eps=c["rms_norm_eps"], activation="silu", tie_embeddings=True,
        lora=LoRAConfig(targets=tuple(c["lora"]["targets"]),
                        r_max=c["lora"]["r_max"],
                        alpha=float(c["lora"]["alpha"])))


@functools.lru_cache(maxsize=None)
def cohort_trainer(cfg, lr: float):
    """The program's vmapped cohort trainer (it returns the routing
    statistics too), one per (configuration, learning rate) in a process,
    so runs of several seeds share its compilation."""
    from repro.fed.client import make_cohort_train
    from repro.optim import adamw
    return make_cohort_train(cfg, adamw(lr), remat=True)


class FedLM(fed.Fed):
    """``fed.Fed`` on instruction traffic: one session, its trainer (with
    routing statistics) and feed, and what the first rounds produced."""

    def __init__(self, cell, seed: int,
                 wrap_train: Optional[Callable] = None):
        import jax
        import jax.numpy as jnp
        from repro.fed.session import FedSession, ServerConfig

        self.cell, self.seed = cell, seed
        c, tr = cell.config, cell.traffic
        self.c, self.tr = c, tr
        self.cfg = model_config(c)
        self.ref = cell.reference()
        self.sizes, self.mix = partition(tr, seed)
        self.cdfs = unigrams(tr, seed, c["vocab_size"])
        self.ranks = fed.client_ranks(tr, seed)
        self.params, lora0 = self.ref.make_params(bench.jax_key(seed), c)
        self.lora0 = jax.tree.map(np.asarray, lora0)
        scfg = ServerConfig(num_clients=int(tr["clients"]),
                            clients_per_round=int(tr["clients_per_round"]),
                            strategy="hlora", svd_method="factored",
                            rank_policy="random",
                            r_min=int(tr["rank_range"][0]),
                            r_max=c["lora"]["r_max"],
                            seed=int(seed) & 0x7FFFFFFF)
        self.session = FedSession(self.cfg, scfg, base_params=self.params,
                                  client_sizes=self.sizes, track_comm=True)
        self.session.ranks = self.ranks.copy()
        self.session.global_lora = jax.tree.map(jnp.asarray, lora0)
        self.head0 = {}
        trainer = cohort_trainer(self.cfg, float(tr["lr"]))
        self.trainer = wrap_train(trainer, self) if wrap_train else trainer
        self.record: List[dict] = []
        self.recording = False
        self.lengths: Dict[int, list] = {}     # round -> (len, pred, cid)
        self.arg_shapes = None                 # the trainer's arguments

    def batches(self, rnd: int, cid: int):
        return client_batches(self.seed, rnd, cid, self.tr,
                              self.mix[cid], self.cdfs)

    def data_fn(self, cohort, rnd):
        import jax
        import jax.numpy as jnp
        with jax.profiler.TraceAnnotation("pb.data"):
            per = [self.batches(int(rnd), int(cid)) for cid in cohort]
            out = {"tokens": jnp.asarray(np.stack([p[0] for p in per])),
                   "labels": jnp.asarray(np.stack([p[1] for p in per]))}
        self.lengths[int(rnd)] = [(n, m, int(cid)) for p, cid
                                  in zip(per, cohort) for n, m in p[2]]
        if self.recording:
            self.record.append({"round": int(rnd),
                                "cohort": np.asarray(cohort).copy()})
        return out

    def train(self, frozen, trainable, masks, batches):
        import jax
        with jax.profiler.TraceAnnotation("pb.train"):
            out = self.trainer(frozen, trainable, masks, batches)
        if self.arg_shapes is None:
            self.arg_shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (frozen, trainable, masks, batches))
        if self.recording:
            rec = self.record[-1]
            rec["losses"] = out[1]
            if len(self.record) == 1:
                rec["start"] = (trainable, masks)
                rec["trained"] = out[0]
        return out


# ---------------------------------------------------------------------------
# The reference's rounds
# ---------------------------------------------------------------------------

def reference_rounds(fl: FedLM, *, fp8: bool = False,
                     precision: str = "highest",
                     rounds: Optional[int] = None) -> dict:
    """The reference following the recorded rounds (cohorts from the
    record; data, ranks and weights from the seed), in the form
    ``fed.readings`` takes; ``fp8`` runs every product of the model in
    float8 (the control), ``precision`` sets the matmul precision."""
    import jax
    ref, c = fl.ref, fl.c
    g = ref.dims(c)
    gi = tuple(sorted(g.items()))
    r_max, alpha = g["r"], g["alpha"]
    glob = fl.lora0
    out = {"losses": [], "start_l": [], "trained_l": [], "gnorm_l": []}
    for i, rec in enumerate(fl.record[:rounds]):
        cohort = rec["cohort"]
        eta = fl.sizes[cohort].astype(np.float64)
        trained, losses = [], []
        for cid in cohort:
            cid = int(cid)
            start = ref.redistribute(glob, int(fl.ranks[cid]), r_max)
            toks, labels, _ = fl.batches(rec["round"], cid)
            fac, _, ls, gm = ref.local_train(
                fl.params, start, toks, labels, float(fl.tr["lr"]), gi=gi,
                fp8=fp8, precision=precision)
            fac = jax.tree.map(lambda x: np.asarray(x, np.float32), fac)
            losses.append(float(np.mean(np.asarray(ls, np.float64))))
            trained.append({t: {**fac[t], "mask": start[t]["mask"]}
                            for t in fac})
            if i == 0:
                out["start_l"].append((start, {}))
                out["trained_l"].append((fac, {}))
                out["gnorm_l"].append(gm)
        out["losses"].append(np.asarray(losses))
        glob = ref.aggregate(trained, fl.ranks[cohort], eta, alpha, r_max)
        if i == 0:
            out["after_first"] = {"lora": glob, "head": {}}
    out["final"] = {"lora": glob, "head": {}}
    return out


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def routed_pairs(session) -> float:
    return float(session.metrics.counter("fed.moe_routed").value)


def trace_counters(fl: FedLM, tr_obj, traced_rounds: List[int],
                   routed: float, peaks: dict) -> dict:
    """What the hybrid readers read of a traced stretch of rounds."""
    c, tr = fl.c, fl.tr
    n = len(traced_rounds)
    seqs = [s for r in traced_rounds for s in fl.lengths[r]]
    real = sum(s[0] for s in seqs)
    total = n * int(tr["clients_per_round"]) * int(tr["local_steps"]) \
        * int(tr["local_batch"]) * int(tr["seq_len"])
    per_round_pairs = routed / n
    flops = flops_hybrid.round_flops(
        c, seqs, fl.ranks, per_round_pairs * n * real / total) / n
    # the compiled trainer's text names each instruction's scope (a
    # persistent-cache hit where the cache is on: the same executable)
    from scopes import scope_seconds
    hlo = cohort_trainer(fl.cfg, float(tr["lr"])).lower(
        *fl.arg_shapes).compile().as_text()
    secs = scope_seconds(tr_obj, hlo, "jit_local_train", SCOPES,
                         ops=(GMM_OP,))
    # the held experts' grouped matmuls at the routed counts: forward and
    # input gradient of each layer's step, each bound on its own
    steps, layers = int(tr["local_steps"]), int(c["num_hidden_layers"])
    per_call = per_round_pairs / (steps * layers)
    f, b = flops_hybrid.expert_gmm_cost(c, per_call)
    from flops import roofline_s
    t_min = 2 * steps * layers * roofline_s(f, b, peaks)[0]
    return {"flops_per_round": flops,
            "moe_s_per_round": (secs.get("moe.route", 0.0)
                                + secs.get("moe.experts", 0.0)) / n,
            "ssd_s_per_round": secs.get("ssm.ssd", 0.0) / n,
            "gmm_s_per_round": secs.get(GMM_OP, 0.0) / n,
            "gmm_roofline_s_per_round": t_min}


def run(cell, *, seed: int, seconds: float, trace: bool, device: dict,
        t_start: float, wrap_train: Optional[Callable] = None):
    import jax
    fl = FedLM(cell, seed, wrap_train=wrap_train)
    fl.first_rounds()
    counter = bench.CompileCounter()
    tr = cell.traffic
    setup_s = time.perf_counter() - t_start

    # -- the window -------------------------------------------------------
    hist: List[dict] = []
    traced, tdir, n_trace, traced_rounds = None, None, 0, []
    routed0 = routed_pairs(fl.session)
    counter.active = True
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        tracing = traced is not None and n_trace < int(tr["trace_rounds"])
        if now >= seconds and not tracing:
            break
        if trace and traced is None and now >= tr["trace_start"] * seconds:
            tdir = tempfile.mkdtemp(prefix="pb_trace_")
            fl.block()
            routed0 = routed_pairs(fl.session)
            jax.profiler.start_trace(tdir)
            traced = jax.profiler.TraceAnnotation("pb.window")
            traced.__enter__()
        if traced is not None and n_trace < int(tr["trace_rounds"]):
            traced_rounds.append(fl.session.rounds_done)
        hist.append(fl.round())
        if traced is not None and n_trace < int(tr["trace_rounds"]):
            n_trace += 1
            if n_trace == int(tr["trace_rounds"]):
                fl.block()
                traced.__exit__(None, None, None)
                jax.profiler.stop_trace()
                routed = routed_pairs(fl.session) - routed0
    fl.block()
    window_s = time.perf_counter() - t0
    counter.active = False
    rounds = len(hist)
    memory_peak = bench.peak_memory_bytes()
    if counter.count:
        print(f"perfbench: {counter.count} compiles inside the window",
              file=sys.stderr)

    # -- what the timed path produced, against the reference -------------
    ref = reference_rounds(fl)
    checks = bench.judge(readings(fl, fed.program_outputs(fl), ref),
                         cell.limits["checks"])
    correct = all(c["ok"] for c in checks.values())
    m = fl.session.metrics
    dev = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": rounds, "failed": 0,
              "device": dev,
              "routing": {"moe_routed": m.counter("fed.moe_routed").value,
                          "moe_dropped": m.counter("fed.moe_dropped").value,
                          "moe_load_max_over_mean":
                              m.gauge("fed.moe_load_max_over_mean").value}}
    if not trace:
        result["metrics"] = bench.e2e_metrics(
            cell, {"round_s": window_s / rounds, "setup_s": setup_s})
        return result, checks

    from xtrace import Trace, load_xspace
    pk = bench.peaks(device["kind"])
    try:
        tr_obj = Trace(load_xspace(tdir), window_span="pb.window")
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    extra = trace_counters(fl, tr_obj, traced_rounds, routed, pk)
    wire = [h["downlink_bytes"][0] + h["uplink_bytes"][0] for h in hist]
    ctx = bench.LayerContext(
        cell=cell, trace=tr_obj, peaks=pk,
        counters={"rounds_traced": n_trace,
                  "wire_bytes_per_round": float(np.mean(wire)), **extra})
    result["metrics"] = bench.read_layer_metrics(ctx)
    dev["busy_s"] = tr_obj.busy_s()
    dev["window_s"] = tr_obj.window_s
    result["breakdown"] = tr_obj.breakdown(fed.SPAN_PREFIXES)
    return result, checks


# ---------------------------------------------------------------------------
# Readings for setting limits (perfbench/control.py)
# ---------------------------------------------------------------------------

def readings(fl: FedLM, prog: dict, ref: dict) -> Dict[str, float]:
    """The compared numbers of ``prog``'s rounds against the reference
    (``fed.readings``), and ``moe_dropped``: the token-expert pairs the
    router sent to a held expert that the layer did not compute, over
    every round the session ran (0: dropless)."""
    out = fed.readings(fl, prog, ref)
    out["moe_dropped"] = float(
        fl.session.metrics.counter("fed.moe_dropped").value)
    return out


def _half_batch(trainer, fl):
    """Fault: the loss of every local batch over the first half of its
    sequence only (the later half's labels left out)."""
    def train(frozen, trainable, masks, data):
        s = data["labels"].shape[-1]
        lab = data["labels"].at[..., s // 2:].set(-1)
        return trainer(frozen, trainable, masks, dict(data, labels=lab))
    return train


def _unchanged(trainer, fl):
    """Fault: a step that returns its state unchanged."""
    def train(frozen, trainable, masks, data):
        out = trainer(frozen, trainable, masks, data)
        return (trainable, *out[1:])
    return train


def _one_leaf(trainer, fl):
    """Fault: one Mamba layer's ``ssm_out`` factor A keeps its start."""
    def train(frozen, trainable, masks, data):
        out = trainer(frozen, trainable, masks, data)
        f = out[0]["factors"]
        a = f["ssm_out"]["A"].at[:, 1].set(
            trainable["factors"]["ssm_out"]["A"][:, 1])
        fac = dict(f, ssm_out=dict(f["ssm_out"], A=a))
        return (dict(out[0], factors=fac), *out[1:])
    return train


def capacity_pairs(held_pairs, group: int, cap: int):
    """``moe._held_pairs`` routing as ``moe_ffn`` does: in each group of
    ``group`` consecutive tokens (one client's step), an expert computes
    only its first ``cap`` pairs in token order; the later ones are left
    out (marked ``held``, as pairs sent elsewhere are)."""
    import jax
    import jax.numpy as jnp

    def pairs(top_i, offset, held):
        key = held_pairs(top_i, offset, held)
        t, k = key.shape
        g = key.reshape(t // group, group * k)
        oh = jax.nn.one_hot(g, held, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(oh, axis=1) - oh) * oh, axis=-1)
        return jnp.where(pos < cap, g, held).reshape(t, k)
    return pairs


def _capacity(trainer, fl):
    """Fault: ``moe_ffn``'s capacity routing in the program's place. Each
    client's step (one group of local_batch x seq_len tokens, as a
    ``moe_ffn`` group of that size) gives an expert ceil(1.25 x tokens x
    top-k / experts) pairs; the rest are dropped. A trainer of its own
    is traced with ``moe._held_pairs`` replaced, in the cohort's folded
    grouped matmul, forward and backward."""
    from repro.fed.client import make_cohort_train
    from repro.models import moe
    from repro.optim import adamw
    cfg, tr = fl.cfg, fl.tr
    group = int(tr["local_batch"]) * int(tr["seq_len"])
    cap = int(np.ceil(group * cfg.experts_per_token * 1.25
                      / cfg.num_experts))
    capped = make_cohort_train(cfg, adamw(float(tr["lr"])), remat=True)
    real = moe._held_pairs

    def train(*args):
        moe._held_pairs = capacity_pairs(real, group, cap)
        try:
            return capped(*args)
        finally:
            moe._held_pairs = real
    return train


FAULTS = {"half_batch": _half_batch, "unchanged": _unchanged,
          "one_leaf": _one_leaf, "capacity": _capacity}
MODES = ("program", "control", "reference_default") + tuple(FAULTS)


def check_readings(cell, seed: int, mode: str) -> Dict[str, float]:
    """The compared numbers of one seed's first rounds, without a window:
    ``program`` as the window's run has them, ``control`` with the
    reference's products in float8 in the program's place,
    ``reference_default`` with the reference at the program's matmul
    precision in its place, or the program with one of ``FAULTS`` planted
    under its trainer."""
    fl = FedLM(cell, seed, wrap_train=FAULTS.get(mode))
    fl.first_rounds()
    ref = reference_rounds(fl)
    if mode == "control":
        prog = reference_rounds(fl, fp8=True)
    elif mode == "reference_default":
        prog = reference_rounds(fl, precision="default")
    else:
        prog = fed.program_outputs(fl)
    return readings(fl, prog, ref)
