"""Tiny stand-ins of the benchmark's configurations and mixes for CPU
tests: the same keys as the real files, widths cut to a few dozen."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def tiny_encoder():
    c = read("perfbench/configs/roberta-large.json")
    c.update(name="encoder-tiny", num_hidden_layers=2, hidden_size=64,
             num_attention_heads=4, intermediate_size=128, vocab_size=512)
    return c


def tiny_fed_traffic(name="sync_mrpc"):
    t = read(f"perfbench/traffic/{name}.json")
    t.update(clients=12, clients_per_round=3, examples=200, local_steps=2,
             local_batch=2, seq_len=32, content_len=[12, 30],
             check_rounds=2, trace_rounds=1)
    return t


def make_root(tmp, cells, like=None):
    """A checkout in ``tmp``: a copy of the benchmark's directory plus the
    given cells, each ``(workload, config, traffic, limits)`` as dicts,
    added as new files and one ``workloads`` entry each. ``like`` names an
    existing cell whose metrics the new cells report too (their names
    join its metrics' ``workloads`` lists)."""
    root = str(tmp)
    dst = os.path.join(root, "perfbench")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    spec = read("BENCHMARK.json")
    for wl, cfg, traffic, limits in cells:
        cfile = f"perfbench/configs/{cfg['name']}.json"
        with open(os.path.join(root, cfile), "w") as f:
            json.dump(cfg, f)
        with open(os.path.join(dst, "traffic", wl["traffic"] + ".json"),
                  "w") as f:
            json.dump(traffic, f)
        with open(os.path.join(dst, "limits", wl["name"] + ".json"),
                  "w") as f:
            json.dump(limits, f)
        if cfg["name"] not in {c["name"] for c in spec["configs"]}:
            spec["configs"].append({"name": cfg["name"], "source": "test",
                                    "file": cfile, "reduced": [],
                                    "why": "test"})
        spec["workloads"].append(wl)
        if like:
            for m in spec["end_to_end"] + spec["per_layer"]:
                if like in m.get("workloads", ()):
                    m["workloads"].append(wl["name"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    return root


def tiny_decoder():
    c = read("perfbench/configs/phi-3-mini.json")
    c.update(name="decoder-tiny", num_hidden_layers=2, hidden_size=64,
             num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=128, vocab_size=512)
    c["lora"] = dict(c["lora"], ranks=[2, 4, 8], r_slab=8, adapters=4)
    c["engine"] = {"page_size": 8, "num_pages": 48, "prefill_chunk": 16,
                   "max_batch": 4, "max_seq": 96, "use_pallas": True}
    return c


# An open-loop chat mix (Poisson arrivals, log-normal lengths): no cell
# runs one yet, the serving driver and the rate sweep take one.
CHAT = {
    "driver": "serve", "why": "open-loop chat", "loop": "open",
    "rate": 0.12,
    "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
               "min": 16, "max": 1536},
    "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
               "min": 8, "max": 511},
    "check_tokens": 400, "check_requests": 6, "check_seconds": 20,
    "trace_start": 0.4, "trace_seconds": 6}


def serve_mix(name):
    return dict(CHAT) if name == "chat" else \
        read(f"perfbench/traffic/{name}.json")


def tiny_serve_traffic(name="rag"):
    t = serve_mix(name)
    t["prompt"] = dict(t["prompt"], min=4, max=60)
    t["output"] = dict(t["output"], min=4, max=24)
    if t["prompt"]["dist"] == "lognormal":
        t["prompt"]["median"], t["output"]["median"] = 20, 8
    t.update(rate=3.0, clients=2, check_tokens=30, check_requests=3,
             check_seconds=2, trace_seconds=1)
    return t
