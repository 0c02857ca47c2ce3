"""Driver for serving cells: multi-tenant LoRA requests through the
program's ``ServeEngine`` (paged KV cache, chunked flash prefill, paged
attention and BGMV decode on the Pallas path) over an ``AdapterRegistry``.

Set-up makes the weights and adapters on the device from the seed, loads
every adapter into its slab slot, builds the engine and serves a warm-up
request (every jitted step and host-side shape). The window then offers
the cell's traffic for ``--seconds``: open loop (Poisson arrivals at a
fixed rate; TTFT from when each request was due) or closed loop (a fixed
number of clients, each sending its next request when the last one
completes). Token times are read on the host after each engine step.
Once the window has closed, the requests still running are finished, the
engine is freed, and the configuration's plain reference scores a seeded
sample of finished requests, the longest among them.
"""
from __future__ import annotations

import gc
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

SPAN_PREFIXES = ("pb.", "serve.")
DRAIN_S = 120.0


# ---------------------------------------------------------------------------
# Traffic: one fixed set of sizes and gaps per mix, ordered by the seed
# ---------------------------------------------------------------------------

def _lognormal_quantiles(n, median, sigma, lo, hi):
    from statistics import NormalDist
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return np.clip(np.round(median * np.exp(sigma * np.asarray(z))),
                   lo, hi).astype(np.int64)


def _uniform_quantiles(n, lo, hi):
    return np.round(lo + (hi - lo) * (np.arange(n) + 0.5) / n
                    ).astype(np.int64)


def _sizes(spec: dict, n: int):
    if spec["dist"] == "lognormal":
        return _lognormal_quantiles(n, spec["median"], spec["sigma"],
                                    spec["min"], spec["max"])
    return _uniform_quantiles(n, spec["min"], spec["max"])


def requests(tr: dict, seed: int, seconds: float, adapters: int):
    """The cell's requests for one run: arrival times (open loop) or
    None (closed loop), prompt and output lengths, adapter indices. The
    multiset of sizes and gaps depends only on the mix and the window;
    the seed picks their order and the prompts' tokens."""
    rng = bench.np_rng(seed, "requests")
    if tr["loop"] == "open":
        n = max(1, int(round(tr["rate"] * seconds)))
        gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / tr["rate"]
        arrivals = np.cumsum(rng.permutation(gaps))
    else:
        n = int(tr["pool"])
        arrivals = None
    prompts = rng.permutation(_sizes(tr["prompt"], n))
    outs = rng.permutation(_sizes(tr["output"], n))
    which = rng.permutation(np.arange(n) % adapters)
    return arrivals, prompts, outs, which


def prompt_tokens(seed: int, i: int, length: int, vocab: int):
    return bench.np_rng(seed, "prompt", i).integers(
        3, vocab, size=int(length)).astype(np.int32)


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def model_config(c: dict):
    from repro.configs.base import LoRAConfig, ModelConfig
    d, h = c["hidden_size"], c["num_attention_heads"]
    return ModelConfig(
        name=c["name"], arch_type="dense",
        num_layers=c["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=c["num_key_value_heads"], head_dim=d // h,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        activation="silu", use_bias=False, rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
        lora=LoRAConfig(targets=tuple(c["lora"]["targets"]),
                        r_max=c["lora"]["r_slab"],
                        alpha=float(c["lora"]["alpha"])))


class Track:
    """Host-side view of one request: when it was due, when each of its
    tokens was seen."""
    __slots__ = ("i", "req", "due", "prompt", "max_new", "adapter", "seen",
                 "times", "done", "prefilled")

    def __init__(self, i, req, due, prompt, max_new, adapter):
        self.i, self.req, self.due = i, req, due
        self.prompt, self.max_new, self.adapter = prompt, max_new, adapter
        self.seen, self.times = 0, []
        self.done = False
        self.prefilled = False


class Serve:
    """One engine over the cell's model and adapters, and its traffic."""

    def __init__(self, cell, seed: int, trace: bool = False,
                 wrap_engine: Optional[Callable] = None):
        import jax
        import jax.numpy as jnp
        from repro.obs import Recorder
        from repro.serve import AdapterRegistry, ServeEngine

        self.cell, self.seed = cell, seed
        c, tr = cell.config, cell.traffic
        self.c, self.tr = c, tr
        self.ref = cell.reference()
        self.cfg = model_config(c)
        lo = c["lora"]
        self.params = self.ref.make_params(bench.jax_key(seed), c)
        ranks = bench.np_rng(seed, "adapter-ranks").choice(
            lo["ranks"], size=int(lo["adapters"]))
        self.adapter_ranks = [int(r) for r in ranks]
        # made on the device, kept on the host: the registry copies each
        # into its slab slot, and the reference reads them back later
        self.adapters = [jax.tree.map(np.asarray, a) for a in
                         self.ref.make_adapters(bench.jax_key(seed, 1), c,
                                                self.adapter_ranks)]
        self.names = [f"tenant{i:02d}" for i in range(len(self.adapters))]
        self.registry = AdapterRegistry(self.cfg, capacity=len(self.names),
                                        r_slab=lo["r_slab"],
                                        dtype=jnp.bfloat16)
        for name, tree in zip(self.names, self.adapters):
            self.registry.register(name, tree)
            self.registry.acquire(name)
            self.registry.release(name)
        e = c["engine"]
        self.recorder = Recorder(annotate=True) if trace else None
        self.engine = ServeEngine(
            self.params, self.cfg, self.registry,
            max_batch=int(e["max_batch"]), max_seq=int(e["max_seq"]),
            kv_mode="paged", page_size=int(e["page_size"]),
            num_pages=int(e["num_pages"]),
            prefill_chunk=int(e["prefill_chunk"]),
            use_pallas=bool(e["use_pallas"]),
            cache_dtype=jnp.bfloat16, recorder=self.recorder)
        if wrap_engine is not None:
            wrap_engine(self.engine)
        self.tracks: List[Track] = []
        self.live: List[Track] = []
        self.steps: List[dict] = []
        jax.block_until_ready(self.params)

    # -- driving ------------------------------------------------------------

    def submit(self, i, due, plen, olen, which) -> Track:
        toks = prompt_tokens(self.seed, i, plen, self.c["vocab_size"])
        self.engine.submit(toks, self.names[which], max_new_tokens=int(olen))
        req = self.engine._queue[-1]
        t = Track(i, req, due, toks, int(olen), int(which))
        self.tracks.append(t)
        self.live.append(t)
        return t

    def step(self, t0: float) -> float:
        """One engine step; the host time the step returned, with every
        new token stamped at it."""
        import jax
        with jax.profiler.TraceAnnotation("pb.step"):
            self.engine.step_batch()
        now = time.perf_counter() - t0
        rec = {"decode": [], "prefill": []}
        still = []
        for tk in self.live:
            n = len(tk.req["out"])      # a preempted request restarts at 0
            if n > tk.seen:
                if not tk.prefilled:
                    tk.prefilled = True
                    rec["prefill"].append(tk.prompt.size)
                first_new = max(tk.seen, 1)
                for j in range(first_new, n):
                    # keys the decode step that made token j attended
                    rec["decode"].append(tk.prompt.size + j)
                tk.times.extend([now] * (n - tk.seen))
                tk.seen = n
            if tk.seen >= tk.max_new:
                tk.done = True
            else:
                still.append(tk)
        self.live = still
        self.steps.append(rec)
        return now

    def warm_up(self) -> None:
        """Serve a few requests: compiles the decode and prefill steps and
        every host-side shape; no request of the window is touched."""
        c = self.c["engine"]
        plen = int(c["prefill_chunk"]) + 7
        for k in range(2):
            toks = prompt_tokens(self.seed, -1 - k, plen,
                                 self.c["vocab_size"])
            self.engine.submit(toks, self.names[k], max_new_tokens=4)
        self.engine.run()

    # -- counters ----------------------------------------------------------

    def preemptions(self) -> int:
        return int(self.engine.preemptions)


def _open_loop(sv: Serve, arrivals, prompts, outs, which, seconds: float,
               on_tick: Callable[[float, bool], None]) -> float:
    n = len(arrivals)
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        on_tick(now, i < n and arrivals[i] <= now)
        if now >= seconds:
            break
        while i < n and arrivals[i] <= now:
            sv.submit(i, arrivals[i], prompts[i], outs[i], which[i])
            i += 1
        if sv.live:
            sv.step(t0)
        else:
            nxt = arrivals[i] if i < n else seconds
            time.sleep(max(0.0, min(nxt, seconds) - now))
    return t0


def _closed_loop(sv: Serve, prompts, outs, which, clients: int,
                 seconds: float, on_tick: Callable[[float, bool], None]
                 ) -> float:
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        on_tick(now, len(sv.live) < clients)
        if now >= seconds:
            break
        while len(sv.live) < clients:
            sv.submit(i, now, prompts[i], outs[i], which[i])
            i += 1
        sv.step(t0)
    return t0


def _drain(sv: Serve, t0: float) -> None:
    t_end = time.perf_counter() + DRAIN_S
    while sv.live and time.perf_counter() < t_end:
        sv.step(t0)


# ---------------------------------------------------------------------------
# The check: the reference over finished requests
# ---------------------------------------------------------------------------

def sample(sv: Serve, tracks: List[Track]) -> List[Track]:
    """A seeded sample of finished requests, the longest first, up to
    ``check_tokens`` served tokens or ``check_requests`` requests."""
    done = [t for t in tracks if t.done]
    if not done:
        return []
    rng = bench.np_rng(sv.seed, "check-sample")
    longest = max(done, key=lambda t: (t.prompt.size + t.max_new, t.i))
    rest = [done[k] for k in rng.permutation(len(done))
            if done[k] is not longest]
    out, toks = [longest], longest.max_new
    for t in rest:
        if toks >= sv.tr["check_tokens"] or \
                len(out) >= sv.tr["check_requests"]:
            break
        out.append(t)
        toks += t.max_new
    return out


def served_tokens(tk: Track) -> np.ndarray:
    return np.asarray(tk.req["out"][:tk.max_new], np.int32)


def logit_gaps(sv: Serve, picked: List[Track], served: List[np.ndarray],
               fp8: bool = False):
    """Per request, per served token: the reference's best logit minus
    its logit of the served token (``fp8``: of the token the reference in
    float8 puts first instead: the control)."""
    import jax.numpy as jnp
    gi = tuple(sorted(sv.ref.dims(sv.c).items()))
    pad = int(sv.c["engine"]["max_seq"])
    gaps = []
    for tk, out in zip(picked, served):
        seq = np.concatenate([tk.prompt, out[:-1]]).astype(np.int32)
        n = seq.size
        full = np.zeros(pad, np.int32)
        full[:n] = seq
        ad = sv.adapters[tk.adapter]
        lg = np.asarray(sv.ref.logits(sv.params, ad, jnp.asarray(full),
                                      gi=gi), np.float64)
        pos = np.arange(tk.prompt.size - 1, n)
        row = lg[pos]
        if fp8:
            lq = np.asarray(sv.ref.logits(sv.params, ad, jnp.asarray(full),
                                          gi=gi, fp8=True))
            pick = np.argmax(lq[pos], -1)
        else:
            pick = out
        gaps.append(row.max(-1) - row[np.arange(len(pos)), pick])
    return gaps


def free_engine(sv: Serve) -> None:
    """Drop the engine's state (KV pools, slabs) before the reference
    runs; the weights and adapters stay, the reference reads them."""
    sv.engine = None
    sv.registry = None
    for tk in sv.tracks:
        tk.req = {"out": list(tk.req["out"])}
    gc.collect()


# ---------------------------------------------------------------------------
# Metrics of the window
# ---------------------------------------------------------------------------

def window_metrics(sv: Serve, tracks: List[Track], seconds: float) -> dict:
    inf = float("inf")
    ttft = [(t.times[0] - t.due) if t.times else inf for t in tracks]
    gaps = []
    tokens = 0
    for t in tracks:
        for a, b in zip(t.times, t.times[1:]):
            if b <= seconds and b > a:
                gaps.append(b - a)
        tokens += sum(1 for x in t.times if x <= seconds)
    return {"ttft_p95_ms": 1e3 * bench.percentile(ttft, 95),
            "itl_p95_ms": 1e3 * bench.percentile(gaps, 95) if gaps else inf,
            "out_tok_s": tokens / seconds}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def run(cell, *, seed: int, seconds: float, trace: bool, device: dict,
        t_start: float, wrap_engine: Optional[Callable] = None):
    import jax
    tr = cell.traffic
    sv = Serve(cell, seed, trace=trace, wrap_engine=wrap_engine)
    sv.warm_up()
    arrivals, prompts, outs, which = requests(tr, seed, seconds,
                                              len(sv.names))
    counter = bench.CompileCounter()
    before = sv.preemptions()
    setup_s = time.perf_counter() - t_start

    state = {"tdir": None, "on": False, "done": False, "steps": [0, 0]}
    t_lo = tr["trace_start"] * seconds

    def on_tick(now, submitting):
        """Trace ``trace_seconds`` from the first submission after
        ``trace_start`` of the window (so the stretch holds a prefill),
        or from a quarter of the window later if none comes."""
        if not trace or state["done"]:
            return
        if not state["on"] and now >= t_lo and (
                submitting or now >= t_lo + 0.25 * seconds):
            state["tdir"] = tempfile.mkdtemp(prefix="pb_trace_")
            jax.profiler.start_trace(state["tdir"])
            state["ann"] = jax.profiler.TraceAnnotation("pb.window")
            state["ann"].__enter__()
            state["on"], state["steps"][0] = True, len(sv.steps)
            state["t_hi"] = now + tr["trace_seconds"]
        elif state["on"] and (now >= state["t_hi"] or now >= seconds):
            state["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            state["on"], state["done"] = False, True
            state["steps"][1] = len(sv.steps)

    counter.active = True
    if tr["loop"] == "open":
        t0 = _open_loop(sv, arrivals, prompts, outs, which, seconds,
                        on_tick)
    else:
        clients = int(tr["clients"])
        t0 = _closed_loop(sv, prompts, outs, which, clients, seconds,
                          on_tick)
    if state["on"]:
        on_tick(float("inf"), False)
    counter.active = False
    preempted = sv.preemptions() - before
    window = [t for t in sv.tracks if t.due < seconds]
    _drain(sv, t0)
    memory_peak = bench.peak_memory_bytes()
    if counter.count:
        print(f"perfbench: {counter.count} compiles inside the window",
              file=sys.stderr)

    failed = sum(1 for t in window if not t.done)
    e2e = window_metrics(sv, window, seconds)

    # -- the check ----------------------------------------------------------
    picked = sample(sv, sv.tracks)
    served = [served_tokens(t) for t in picked]
    free_engine(sv)
    gaps = logit_gaps(sv, picked, served)
    worst = max((float(g.max()) for g in gaps), default=float("inf"))
    checks = bench.judge({"logit_gap": worst}, cell.limits["checks"])
    correct = all(c["ok"] for c in checks.values()) and failed == 0

    dev = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": len(window),
              "failed": failed, "device": dev}
    if not trace:
        result["metrics"] = bench.e2e_metrics(cell,
                                              dict(e2e, setup_s=setup_s))
        return result, checks

    from xtrace import Trace, load_xspace
    try:
        tobj = Trace(load_xspace(state["tdir"]), window_span="pb.window")
    finally:
        shutil.rmtree(state["tdir"], ignore_errors=True)
    s0, s1 = state["steps"]
    traced = sv.steps[s0:s1]
    mean_rank = float(np.mean(sv.adapter_ranks))
    c = cell.config
    pre = [p for st in traced for p in st["prefill"]]
    dec = [x for st in traced for x in st["decode"]]
    work = (sum(flops_lib.prefill_flops(c, p, mean_rank) for p in pre)
            + sum(flops_lib.decode_flops(c, x, mean_rank) for x in dec))
    ctx = bench.LayerContext(
        cell=cell, trace=tobj, peaks=bench.peaks(device["kind"]),
        counters={"preemptions": preempted, "flops": work})
    result["metrics"] = bench.read_layer_metrics(ctx)
    dev["busy_s"] = tobj.busy_s()
    dev["window_s"] = tobj.window_s
    result["breakdown"] = tobj.breakdown(SPAN_PREFIXES)
    return result, checks


# ---------------------------------------------------------------------------
# Readings for setting limits (perfbench/control.py)
# ---------------------------------------------------------------------------

def _alter_token(engine):
    """Fault: every request's third token is altered where it is made."""
    step = engine.step_batch

    def altered():
        step()
        for row in engine._rows:
            if row is not None and len(row["out"]) == 3:
                row["out"][2] = (row["out"][2] + 1) % engine.cfg.vocab_size
    engine.step_batch = altered


FAULTS = {"alter_token": _alter_token}
MODES = ("program", "control") + tuple(FAULTS)


def check_readings(cell, seed: int, mode: str) -> Dict[str, float]:
    """The widest logit gap of a run at the cell's own load, with a
    window of ``check_seconds``: the program's; with ``control`` the
    control's in its place (the float8 reference's first choices at the
    same positions), the program's beside it as ``program_logit_gap``; or
    the program's with one of ``FAULTS`` planted."""
    tr = cell.traffic
    sv = Serve(cell, seed, wrap_engine=FAULTS.get(mode))
    sv.warm_up()
    seconds = float(tr["check_seconds"])
    arrivals, prompts, outs, which = requests(tr, seed, seconds,
                                              len(sv.names))
    if tr["loop"] == "open":
        t0 = _open_loop(sv, arrivals, prompts, outs, which, seconds,
                        lambda now, submitting: None)
    else:
        t0 = _closed_loop(sv, prompts, outs, which, int(tr["clients"]),
                          seconds, lambda now, submitting: None)
    _drain(sv, t0)
    picked = sample(sv, sv.tracks)
    served = [served_tokens(t) for t in picked]
    free_engine(sv)
    out = {"logit_gap": max(float(g.max()) for g in
                            logit_gaps(sv, picked, served)),
           "tokens": int(sum(s.size for s in served)),
           "requests": len(picked)}
    if mode == "control":
        out["program_logit_gap"] = out["logit_gap"]
        out["logit_gap"] = max(
            float(g.max()) for g in logit_gaps(sv, picked, served, fp8=True))
    return out
