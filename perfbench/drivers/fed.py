"""Driver for federated cells: synchronous HLoRA rounds through the
program's ``FedSession`` + ``SyncRound`` with the vmapped cohort trainer,
factored aggregation and the wire round trip.

Set-up builds the one session and trainer the window drives, from the
seed, and runs its first ``check_rounds`` rounds through the window's own
call and feed (they compile and warm every program). The window then runs
whole rounds until ``--seconds`` have passed: ``round_s`` is its wall time
over its rounds. Once it has closed, the configuration's plain reference
follows the first rounds from the same weights, data, cohorts and ranks,
and the compared numbers are judged against the cell's limits.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import bench  # noqa: E402
import flops as flops_lib  # noqa: E402

CLS, SEP, PAD, FIRST_WORD = 2, 1, 0, 3
SPAN_PREFIXES = ("pb.",)


# ---------------------------------------------------------------------------
# Traffic: clients, shards, sentence pairs
# ---------------------------------------------------------------------------

def partition(tr: dict, seed: int):
    """Client sizes and label mixes: a Dirichlet(alpha) split of each
    class's examples over the clients, plus a floor of examples each."""
    rng = bench.np_rng(seed, "partition")
    n, k = int(tr["examples"]), int(tr["clients"])
    per_class = [n // 2, n - n // 2]
    counts = np.zeros((k, 2), np.int64)
    for c, nc in enumerate(per_class):
        props = rng.dirichlet([tr["dirichlet_alpha"]] * k)
        counts[:, c] = np.floor(props * nc).astype(np.int64)
    floor = int(tr.get("min_examples", 0))
    counts += floor // 2
    counts[:, 1] += floor - 2 * (floor // 2)
    sizes = counts.sum(1)
    mix = (counts + 0.5) / (sizes[:, None] + 1.0)
    return sizes, mix[:, 1]


def sentence_pairs(rng, n: int, tr: dict, vocab: int, p_pos: float):
    """``n`` paraphrase-style pairs: [CLS] s1 [SEP] s2 [SEP], padded to
    ``seq_len``; a positive s2 is a shuffled copy of s1 with ``noise`` of
    its words resampled, a negative one is drawn afresh."""
    s = int(tr["seq_len"])
    lo, hi = tr["content_len"]
    toks = np.full((n, s), PAD, np.int32)
    labels = (rng.random(n) < p_pos).astype(np.int32)
    lens = rng.integers(lo, hi + 1, size=n)
    for i in range(n):
        m = int(lens[i]) - 3
        a = m // 2
        b = m - a
        s1 = rng.integers(FIRST_WORD, vocab, size=a)
        if labels[i]:
            s2 = rng.permutation(np.resize(s1, b))
            noise = rng.random(b) < tr["noise"]
            s2 = np.where(noise, rng.integers(FIRST_WORD, vocab, size=b), s2)
        else:
            s2 = rng.integers(FIRST_WORD, vocab, size=b)
        toks[i, :m + 3] = np.concatenate([[CLS], s1, [SEP], s2, [SEP]])
    return toks, labels


def client_batches(seed: int, rnd: int, cid: int, tr: dict, vocab: int,
                   p_pos: float):
    """(steps, batch, seq) tokens and (steps, batch) labels, fresh rows
    for every (round, client)."""
    rng = bench.np_rng(seed, "rows", rnd, cid)
    st, b = int(tr["local_steps"]), int(tr["local_batch"])
    toks, labels = sentence_pairs(rng, st * b, tr, vocab, p_pos)
    return toks.reshape(st, b, -1), labels.reshape(st, b)


def client_ranks(tr: dict, seed: int) -> np.ndarray:
    lo, hi = tr["rank_range"]
    return bench.np_rng(seed, "ranks").integers(
        lo, hi + 1, size=int(tr["clients"])).astype(np.int32)


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def model_config(c: dict):
    from repro.configs.base import LoRAConfig, ModelConfig
    d, h = c["hidden_size"], c["num_attention_heads"]
    return ModelConfig(
        name=c["name"], arch_type="encoder",
        num_layers=c["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=h, head_dim=d // h, d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], num_classes=c["num_labels"],
        activation="gelu", use_bias=True, rope_theta=0.0,
        lora=LoRAConfig(targets=tuple(c["lora"]["targets"]),
                        r_max=c["lora"]["r_max"],
                        alpha=float(c["lora"]["alpha"])))


class Fed:
    """One session, its trainer and feed, and what the first rounds
    produced (for the check)."""

    def __init__(self, cell, seed: int,
                 wrap_train: Optional[Callable] = None):
        import jax
        import jax.numpy as jnp
        from repro.fed.client import make_cohort_train
        from repro.fed.session import FedSession, ServerConfig
        from repro.optim import adamw

        self.cell, self.seed = cell, seed
        c, tr = cell.config, cell.traffic
        self.c, self.tr = c, tr
        self.ref = cell.reference()
        self.cfg = model_config(c)
        self.sizes, self.p_pos = partition(tr, seed)
        self.ranks = client_ranks(tr, seed)
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
        self.head0 = {k: np.asarray(v)
                      for k, v in self.session.global_head.items()}
        trainer = make_cohort_train(self.cfg, adamw(float(tr["lr"])))
        self.trainer = wrap_train(trainer) if wrap_train else trainer
        self.record: List[dict] = []       # per round while recording
        self.recording = False

    # -- the window's own call and feed ------------------------------------

    def data_fn(self, cohort, rnd):
        import jax
        import jax.numpy as jnp
        with jax.profiler.TraceAnnotation("pb.data"):
            v = self.c["vocab_size"]
            per = [client_batches(self.seed, rnd, int(cid), self.tr, v,
                                  float(self.p_pos[int(cid)]))
                   for cid in cohort]
            out = {"tokens": jnp.asarray(np.stack([p[0] for p in per])),
                   "labels": jnp.asarray(np.stack([p[1] for p in per]))}
        if self.recording:
            self.record.append({"round": int(rnd),
                                "cohort": np.asarray(cohort).copy()})
        return out

    def train(self, frozen, trainable, masks, batches):
        import jax
        with jax.profiler.TraceAnnotation("pb.train"):
            out, losses = self.trainer(frozen, trainable, masks, batches)
        if self.recording:
            rec = self.record[-1]
            rec["losses"] = losses
            if len(self.record) == 1:
                rec["start"] = (trainable, masks)
                rec["trained"] = out
        return out, losses

    def round(self):
        import jax
        from repro.fed.schedulers import SyncRound
        with jax.profiler.TraceAnnotation("pb.round"):
            return SyncRound().run(self.session, self.train, self.data_fn, 1)

    def block(self):
        import jax
        jax.block_until_ready((self.session.global_lora,
                               self.session.global_head))

    # -- set-up ---------------------------------------------------------

    def global_state(self) -> dict:
        import jax
        return {"lora": jax.tree.map(np.asarray, self.session.global_lora),
                "head": {k: np.asarray(v)
                         for k, v in self.session.global_head.items()}}

    def first_rounds(self) -> None:
        import jax
        self.recording = True
        for i in range(int(self.tr["check_rounds"])):
            self.round()
            if i == 0:
                self.after_first = self.global_state()
        self.block()
        self.recording = False
        self.final = self.global_state()
        for rec in self.record:
            rec["losses"] = np.asarray(rec["losses"])
        r0 = self.record[0]
        r0["start"] = jax.tree.map(np.asarray, r0["start"])
        r0["trained"] = jax.tree.map(np.asarray, r0["trained"])


# ---------------------------------------------------------------------------
# The reference's rounds and the compared numbers
# ---------------------------------------------------------------------------

def reference_rounds(fed: Fed, *, fp8: bool = False,
                     precision: str = "highest") -> dict:
    """The reference following the recorded rounds (cohorts from the
    record; data, ranks and weights from the seed); ``fp8`` runs it with
    every matrix product in float8 (the control), ``precision`` sets its
    products' matmul precision."""
    import jax
    import jax.numpy as jnp
    ref, c, tr = fed.ref, fed.c, fed.tr
    g = ref.dims(c)
    gi = tuple(sorted(g.items()))
    r_max, alpha = g["r"], g["alpha"]
    frozen = {k: v for k, v in fed.params.items()
              if k not in ("cls_head", "cls_bias")}
    glob = fed.lora0
    head = fed.head0
    out = {"losses": [], "start_l": [], "trained_l": [], "gnorm_l": []}
    for i, rec in enumerate(fed.record):
        cohort = rec["cohort"]
        eta = fed.sizes[cohort].astype(np.float64)
        trained, heads, losses = [], [], []
        for cid in cohort:
            cid = int(cid)
            rk = int(fed.ranks[cid])
            start = ref.redistribute(glob, rk, r_max)
            toks, labels = client_batches(fed.seed, rec["round"], cid, tr,
                                          c["vocab_size"],
                                          float(fed.p_pos[cid]))
            fac, hd, ls, gm = ref.local_train(
                frozen, jax.tree.map(jnp.asarray, start),
                jax.tree.map(jnp.asarray, head), jnp.asarray(toks),
                jnp.asarray(labels), jnp.float32(tr["lr"]), gi=gi, fp8=fp8,
                precision=precision)
            fac = jax.tree.map(lambda x: np.asarray(x, np.float32), fac)
            hd = {k: np.asarray(v, np.float32) for k, v in hd.items()}
            losses.append(float(np.mean(np.asarray(ls, np.float64))))
            trained.append({t: {**fac[t], "mask": start[t]["mask"]}
                            for t in fac})
            heads.append(hd)
            if i == 0:
                out["start_l"].append((start, head))
                out["trained_l"].append((fac, hd))
                out["gnorm_l"].append(jax.tree.map(np.asarray, gm))
        out["losses"].append(np.asarray(losses))
        w = eta / eta.sum()
        head = {k: np.tensordot(w, np.stack([h[k] for h in heads]), 1
                                ).astype(np.float32) for k in head}
        glob = ref.aggregate(trained, fed.ranks[cohort], eta, alpha, r_max)
        if i == 0:
            out["after_first"] = {"lora": glob, "head": head}
    out["final"] = {"lora": glob, "head": head}
    return out


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               keep: Optional[set] = None) -> np.ndarray:
    """Per leaf: |‖prog‖ − ‖ref‖| over max(‖ref‖ of the leaf, the median
    leaf's ‖ref‖, 1e-9: where no leaf moved, round-off reads as none)."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return np.asarray([abs(prog[n] - ref[n]) / max(ref[n], med, 1e-9)
                       for n in names])


def _change_norms(start, trained, r_max) -> Dict[str, float]:
    """Per (target, factor, layer) and head leaf: ‖trained − start‖."""
    (fs, hs), (ft, ht) = start, trained
    out = {}
    for t in ft:
        for f in ("A", "B"):
            dd = np.asarray(ft[t][f], np.float64) - np.asarray(
                fs[t][f], np.float64)
            for l in range(dd.shape[0]):
                out[f"{t}.{f}.{l}"] = float(np.linalg.norm(dd[l]))
    for k in ht:
        out[f"head.{k}"] = float(np.linalg.norm(
            np.asarray(ht[k], np.float64) - np.asarray(hs[k], np.float64)))
    return out


def _grad_norms(gm) -> Dict[str, float]:
    fac, head = gm
    out = {}
    for t in fac:
        for f in ("A", "B"):
            for l, v in enumerate(np.asarray(fac[t][f])):
                out[f"{t}.{f}.{l}"] = float(v)
    for k, v in head.items():
        out[f"head.{k}"] = float(v)
    return out


def _global_norms(final, head0, alpha) -> Dict[str, float]:
    out = {}
    for t, ad in final["lora"].items():
        m = np.asarray(ad["mask"], np.float64)
        a = np.asarray(ad["A"], np.float64) * m[:, None, :]
        b = np.asarray(ad["B"], np.float64) * m[:, :, None]
        scale = alpha / np.maximum(m.sum(-1), 1.0)
        for l in range(a.shape[0]):
            out[f"{t}.dW.{l}"] = float(scale[l] * np.linalg.norm(a[l] @ b[l]))
    for k, v in final["head"].items():
        out[f"head.{k}"] = float(np.linalg.norm(
            np.asarray(v, np.float64) - np.asarray(head0[k], np.float64)))
    return out


def program_outputs(fed: Fed) -> dict:
    """What the timed path produced in its first rounds, in the form
    the readings take."""
    r0 = fed.record[0]
    (start_tr, masks), trained = r0["start"], r0["trained"]
    starts, outs = [], []
    for k in range(len(r0["cohort"])):
        fs = {t: {f: start_tr["factors"][t][f][k] for f in ("A", "B")}
              for t in start_tr["factors"]}
        hs = {h: v[k] for h, v in start_tr["head"].items()}
        ft = {t: {f: trained["factors"][t][f][k] for f in ("A", "B")}
              for t in trained["factors"]}
        ht = {h: v[k] for h, v in trained["head"].items()}
        starts.append((fs, hs))
        outs.append((ft, ht))
    return {"losses": [np.asarray(r["losses"], np.float64)
                       for r in fed.record],
            "start_l": starts, "trained_l": outs,
            "after_first": fed.after_first, "final": fed.final}


def reference_aggregate(fed: Fed, prog: dict) -> dict:
    """The reference's aggregation of the clients' round-1 outputs as
    ``prog`` produced them: the global adapter that round 1 should have
    left, given those inputs."""
    g = fed.ref.dims(fed.c)
    cohort = fed.record[0]["cohort"]
    return fed.ref.aggregate([ft for ft, _ in prog["trained_l"]],
                             fed.ranks[cohort],
                             fed.sizes[cohort].astype(np.float64),
                             g["alpha"], g["r"])


def readings(fed: Fed, prog: dict, ref: dict) -> Dict[str, float]:
    """The compared numbers: ``loss`` (the widest gap, in nats, between
    a client's mean local loss in round 1 and the reference's),
    ``client_change`` (the median leaf's gap of the norm of its change
    over round 1's local steps, worst client), ``client_change_leaf``
    (each leaf's gap averaged over the clients, worst leaf: a fault in
    one leaf reads 1 on every client, rounding does not),
    ``aggregate_worst_leaf`` and ``aggregate_median_leaf`` (the worst and
    the median leaf's gap between the global adapter's effective update
    that round 1 left and the reference's aggregation of the same
    clients' outputs) and ``global_change`` (the median leaf's gap of the
    norm of the global adapter's effective update and of the head's
    change after the compared rounds). Beside them, for the record: the
    later rounds' loss gap, the worst leaf of the client and global
    changes, and where each worst leaf lies."""
    g = fed.ref.dims(fed.c)
    r_max, alpha = g["r"], g["alpha"]
    gaps = [np.abs(np.asarray(p, np.float64) - np.asarray(r, np.float64))
            for p, r in zip(prog["losses"], ref["losses"])]
    med_c, worst_c, where_c = 0.0, 0.0, ""
    per_leaf: Dict[str, List[float]] = {}
    for k in range(len(ref["trained_l"])):
        rn = _change_norms(ref["start_l"][k], ref["trained_l"][k], r_max)
        pn = _change_norms(prog["start_l"][k], prog["trained_l"][k], r_max)
        gn = _grad_norms(ref["gnorm_l"][k])
        med = float(np.median(list(gn.values())))
        keep = {n for n, v in gn.items() if v >= 1e-3 * med}
        names = [n for n in rn if n in keep]
        leaf = _leaf_gaps(pn, rn, keep)
        for n, v in zip(names, leaf):
            per_leaf.setdefault(n, []).append(float(v))
        med_c = max(med_c, float(np.median(leaf)))
        if leaf.max() > worst_c:
            n = names[int(leaf.argmax())]
            worst_c = float(leaf.max())
            where_c = (f"client {k} leaf {n}: grad {gn[n] / med:.3g} x "
                       f"median, change {rn[n]:.3g} (ref) {pn[n]:.3g}")
    ag_r = _global_norms({"lora": reference_aggregate(fed, prog),
                          "head": {}}, fed.head0, alpha)
    ga = _leaf_gaps(_global_norms(prog["after_first"], fed.head0, alpha),
                    ag_r)
    gp = _global_norms(prog["final"], fed.head0, alpha)
    gr = _global_norms(ref["final"], fed.head0, alpha)
    gg = _leaf_gaps(gp, gr)
    gn_ = list(gr)[int(gg.argmax())]
    return {"loss": float(gaps[0].max()),
            "client_change": med_c,
            "client_change_leaf": max(sum(v) / len(v)
                                      for v in per_leaf.values()),
            "aggregate_worst_leaf": float(ga.max()),
            "aggregate_median_leaf": float(np.median(ga)),
            "global_change": float(np.median(gg)),
            "loss_later_rounds": float(max((x.max() for x in gaps[1:]),
                                           default=0.0)),
            "client_change_worst_leaf": worst_c,
            "global_change_worst_leaf": float(gg.max()),
            "where_client_change_worst_leaf": where_c,
            "where_aggregate_worst_leaf": list(ag_r)[int(ga.argmax())],
            "where_global_change_worst_leaf":
                f"{gn_}: {gr[gn_]:.3g} (ref) {gp[gn_]:.3g}"}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def run(cell, *, seed: int, seconds: float, trace: bool, device: dict,
        t_start: float, wrap_train: Optional[Callable] = None):
    import jax
    fed = Fed(cell, seed, wrap_train=wrap_train)
    fed.first_rounds()
    counter = bench.CompileCounter()
    tr = cell.traffic
    setup_s = time.perf_counter() - t_start

    # -- the window -------------------------------------------------------
    hist: List[dict] = []
    traced, tdir, n_trace = None, None, 0
    counter.active = True
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        tracing = traced is not None and n_trace < int(tr["trace_rounds"])
        if now >= seconds and not tracing:
            break
        if trace and traced is None and now >= tr["trace_start"] * seconds:
            tdir = tempfile.mkdtemp(prefix="pb_trace_")
            fed.block()
            jax.profiler.start_trace(tdir)
            traced = jax.profiler.TraceAnnotation("pb.window")
            traced.__enter__()
        hist.append(fed.round())
        if traced is not None and n_trace < int(tr["trace_rounds"]):
            n_trace += 1
            if n_trace == int(tr["trace_rounds"]):
                fed.block()
                traced.__exit__(None, None, None)
                jax.profiler.stop_trace()
    fed.block()
    window_s = time.perf_counter() - t0
    counter.active = False
    rounds = len(hist)
    memory_peak = bench.peak_memory_bytes()
    if counter.count:
        print(f"perfbench: {counter.count} compiles inside the window",
              file=sys.stderr)

    # -- what the timed path produced, against the reference -------------
    ref = reference_rounds(fed)
    checks = bench.judge(readings(fed, program_outputs(fed), ref),
                         cell.limits["checks"])
    correct = all(c["ok"] for c in checks.values())

    dev = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": rounds, "failed": 0,
              "device": dev}
    if not trace:
        result["metrics"] = bench.e2e_metrics(
            cell, {"round_s": window_s / rounds, "setup_s": setup_s})
        return result, checks

    from xtrace import Trace, load_xspace
    try:
        tr_obj = Trace(load_xspace(tdir), window_span="pb.window")
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    wire = [h["downlink_bytes"][0] + h["uplink_bytes"][0] for h in hist]
    ctx = bench.LayerContext(
        cell=cell, trace=tr_obj, peaks=bench.peaks(device["kind"]),
        counters={
            "rounds_traced": n_trace,
            "wire_bytes_per_round": float(np.mean(wire)),
            "flops_per_round": flops_lib.encoder_round_flops(
                cell.config, int(tr["clients_per_round"]),
                int(tr["local_steps"]), int(tr["local_batch"]),
                int(tr["seq_len"]),
                [int(r) for r in fed.ranks])})
    result["metrics"] = bench.read_layer_metrics(ctx)
    dev["busy_s"] = tr_obj.busy_s()
    dev["window_s"] = tr_obj.window_s
    result["breakdown"] = tr_obj.breakdown(SPAN_PREFIXES)
    return result, checks


# ---------------------------------------------------------------------------
# Readings for setting limits (perfbench/control.py)
# ---------------------------------------------------------------------------

def _half_batch(trainer):
    """Fault: half of every local batch left out, the mean over the rest."""
    def train(frozen, trainable, masks, data):
        b = data["labels"].shape[2]
        return trainer(frozen, trainable, masks,
                       {k: v[:, :, :b // 2] for k, v in data.items()})
    return train


def _unchanged(trainer):
    """Fault: a step that returns its state unchanged."""
    def train(frozen, trainable, masks, data):
        _, losses = trainer(frozen, trainable, masks, data)
        return trainable, losses
    return train


FAULTS = {"half_batch": _half_batch, "unchanged": _unchanged}
MODES = ("program", "control", "reference_default") + tuple(FAULTS)


def check_readings(cell, seed: int, mode: str) -> Dict[str, float]:
    """The compared numbers of one seed's first rounds, without a window:
    ``program`` as the window's run has them, ``control`` with the
    reference's products in float8 in the program's place,
    ``reference_default`` with the reference at the program's matmul
    precision in its place (how far rounding alone moves each number),
    or the program with one of ``FAULTS`` planted under its trainer."""
    fed = Fed(cell, seed, wrap_train=FAULTS.get(mode))
    fed.first_rounds()
    ref = reference_rounds(fed)
    if mode == "control":
        prog = reference_rounds(fed, fp8=True)
    elif mode == "reference_default":
        prog = reference_rounds(fed, precision="default")
    else:
        prog = program_outputs(fed)
    return readings(fed, prog, ref)
