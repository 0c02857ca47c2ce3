"""Wire codecs (fed/compress.py): round-trip error bounds, self-describing
decode, measured byte ordering, and session-level integration.

The codecs live *inside* the measured wire format, so every property here
is asserted on real serialized messages where it matters: ``num_bytes``
stays the length of the actual buffer, and a receiver decodes from the
header alone (no out-of-band codec configuration).
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_reduced
from repro.fed import (Bf16Codec, FedSession, Int8Codec, ServerConfig,
                       SimConfig, TopKCodec, codec_from_name, run_experiment)
from repro.fed import messages as msg_lib
from repro.fed.simulation import pretrain_backbone

ALPHA_SIM = SimConfig(task="mrpc", num_examples=512, eval_examples=128,
                      rounds=3, local_steps=2, local_batch=8,
                      pretrain_steps=20, lr=1e-3, seed=0)


@pytest.fixture(scope="module")
def cfg():
    return get_reduced("roberta-large")


@pytest.fixture(scope="module")
def base(cfg):
    return pretrain_backbone(cfg, ALPHA_SIM)


def _adapter(seed, layers=2, d_in=6, d_out=5, r=4):
    """A float32 payload with a spread of magnitudes per rank direction —
    the shape real truncated factors have on the wire."""
    rng = np.random.default_rng(seed)
    a = (rng.standard_normal((layers, d_in, r))
         * np.geomspace(1.0, 0.01, r)).astype(np.float32)
    b = (rng.standard_normal((layers, r, d_out))
         * np.geomspace(1.0, 0.01, r)[:, None]).astype(np.float32)
    return {"q": {"A": a, "B": b}, "v": {"A": 2 * a, "B": 0.5 * b}}


def _roundtrip(codec, adapter):
    arrays, meta = codec.encode_adapter(adapter)
    # meta must be JSON-safe: it rides in the wire header
    import json
    json.dumps(meta)
    return codec.decode_adapter(arrays, meta)


# ---------------------------------------------------------------------------
# Property tests: quantization error bounds / top-k exactness
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), r=st.integers(1, 8),
       layers=st.integers(1, 3))
def test_int8_error_bounded_by_half_scale(seed, r, layers):
    adapter = _adapter(seed, layers=layers, r=r)
    codec = Int8Codec()
    arrays, meta = codec.encode_adapter(adapter)
    back = codec.decode_adapter(arrays, meta)
    for t, ad in adapter.items():
        for leaf in ("A", "B"):
            assert arrays[f"{t}/{leaf}"].dtype == np.int8
            scale = meta[t][f"{leaf}_scale"]
            err = np.abs(back[t][leaf] - ad[leaf])
            assert err.max() <= scale / 2 + 1e-7, (t, leaf)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), r=st.integers(1, 8))
def test_bf16_relative_error_bounded(seed, r):
    adapter = _adapter(seed, r=r)
    back = _roundtrip(Bf16Codec(), adapter)
    for t, ad in adapter.items():
        for leaf in ("A", "B"):
            err = np.abs(back[t][leaf] - ad[leaf])
            assert (err <= 2.0 ** -8 * np.abs(ad[leaf]) + 1e-12).all()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), r=st.integers(1, 8), k=st.integers(1, 10))
def test_topk_kept_directions_exact_dropped_zero(seed, r, k):
    adapter = _adapter(seed, r=r)
    codec = TopKCodec(k=k)
    arrays, meta = codec.encode_adapter(adapter)
    back = codec.decode_adapter(arrays, meta)
    for t, ad in adapter.items():
        keep = np.asarray(meta[t]["keep"], np.int64)
        assert len(keep) == min(k, r)
        assert (np.diff(keep) > 0).all() if len(keep) > 1 else True
        # kept columns cross the wire bit-exactly; dropped ones decode to
        # exact zeros (the truncate→pad invariant the session relies on)
        np.testing.assert_array_equal(back[t]["A"][..., keep],
                                      ad["A"][..., keep])
        np.testing.assert_array_equal(back[t]["B"][..., keep, :],
                                      ad["B"][..., keep, :])
        dropped = np.setdiff1d(np.arange(r), keep)
        assert not np.any(back[t]["A"][..., dropped])
        assert not np.any(back[t]["B"][..., dropped, :])
        if k >= r:    # full rank: the codec is lossless
            np.testing.assert_array_equal(back[t]["A"], ad["A"])
            np.testing.assert_array_equal(back[t]["B"], ad["B"])


def test_topk_keeps_highest_energy_directions():
    """With per-direction energies spanning orders of magnitude the kept
    set must be exactly the top-k by ‖A_j‖·‖B_j‖."""
    adapter = _adapter(7, r=8)
    a, b = adapter["q"]["A"], adapter["q"]["B"]
    score = (np.linalg.norm(a.reshape(-1, 8), axis=0)
             * np.linalg.norm(np.swapaxes(b, -2, -1).reshape(-1, 8), axis=0))
    _, meta = TopKCodec(k=3).encode_adapter({"q": adapter["q"]})
    want = np.sort(np.argsort(-score)[:3])
    np.testing.assert_array_equal(np.asarray(meta["q"]["keep"]), want)


# ---------------------------------------------------------------------------
# Wire integration: self-describing headers, measured bytes
# ---------------------------------------------------------------------------

def _update(codec, seed=0, r=8):
    return msg_lib.ClientUpdate(
        client_id=3, start_version=5, num_examples=64,
        adapter=_adapter(seed, layers=2, d_in=16, d_out=12, r=r),
        head={"cls": np.arange(6, dtype=np.float32)}, codec=codec)


def test_wire_self_describing_decode():
    """The receiver reconstructs from bytes alone — no codec object."""
    for codec, tol in ((Int8Codec(), 2e-2), (Bf16Codec(), 1e-2),
                       (TopKCodec(k=8), 0.0)):
        msg = _update(codec)
        back = msg_lib.ClientUpdate.from_bytes(msg.to_bytes())
        assert back.codec is None        # nothing but the header needed
        assert back.num_examples == 64 and back.start_version == 5
        for t, ad in msg.adapter.items():
            for leaf in ("A", "B"):
                got = np.asarray(back.adapter[t][leaf], np.float64)
                want = np.asarray(ad[leaf], np.float64)
                assert np.abs(got - want).max() <= \
                    tol * max(np.abs(want).max(), 1e-9) + 1e-12
        np.testing.assert_array_equal(back.head["cls"], msg.head["cls"])


def test_wire_bytes_ordering_and_none_identity():
    raw = _update(None)
    sizes = {name: _update(codec_from_name(name)).num_bytes
             for name in ("none", "int8", "bf16", "topk:2")}
    # codec=None is *byte-identical* to the codec-less format (golden-safe)
    assert sizes["none"] == raw.num_bytes
    assert _update(codec_from_name("none")).to_bytes() == raw.to_bytes()
    assert sizes["int8"] < sizes["bf16"] < sizes["none"]
    assert sizes["topk:2"] < sizes["none"]
    # every num_bytes is the real buffer length
    for name in sizes:
        m = _update(codec_from_name(name))
        assert m.num_bytes == len(m.to_bytes())


def test_codec_from_name_resolution():
    assert codec_from_name(None) is None
    assert codec_from_name("none") is None
    assert isinstance(codec_from_name("bf16"), Bf16Codec)
    assert isinstance(codec_from_name("int8"), Int8Codec)
    assert codec_from_name("topk").k == 4
    assert codec_from_name("topk:6").k == 6
    c = TopKCodec(k=2)
    assert codec_from_name(c) is c
    with pytest.raises(ValueError, match="unknown wire codec"):
        codec_from_name("zstd")
    with pytest.raises(ValueError, match="k >= 1"):
        TopKCodec(k=0)


# ---------------------------------------------------------------------------
# Session integration: codec applied to every message, bytes shrink
# ---------------------------------------------------------------------------

def test_topk_full_rank_session_broadcast_lossless(cfg, base):
    """topk at k=r_max through the session's wire path reconstructs the
    exact same cohort tree as the raw format."""
    scfg = ServerConfig(num_clients=4, clients_per_round=4,
                        strategy="hlora", rank_policy="random",
                        r_min=2, r_max=8, seed=0)
    sess_raw = FedSession(cfg, scfg, base, client_sizes=[64] * 4)
    sess_tk = FedSession(cfg, scfg, base, client_sizes=[64] * 4,
                         codec="topk:8")
    cohort = np.arange(4)
    tree_raw, _ = sess_raw.broadcast_cohort(cohort)
    tree_tk, _ = sess_tk.broadcast_cohort(cohort)
    for t in tree_raw:
        for leaf in ("A", "B", "mask"):
            np.testing.assert_array_equal(
                np.asarray(tree_tk[t][leaf]), np.asarray(tree_raw[t][leaf]),
                err_msg=(t, leaf))


def test_session_codec_shrinks_wire_and_trains(cfg, base):
    """ServerConfig.codec applies to every broadcast/update: int8 runs
    end-to-end to finite losses at ~4x less measured wire traffic."""
    sim = SimConfig(**{**ALPHA_SIM.__dict__, "rounds": 2})
    byts = {}
    for codec in ("none", "int8"):
        scfg = ServerConfig(num_clients=8, clients_per_round=4,
                            strategy="hlora", rank_policy="random",
                            r_min=2, r_max=8, seed=0, codec=codec)
        h = run_experiment(cfg, sim, scfg, base_params=base)
        assert np.isfinite(h["train_loss"]).all(), codec
        byts[codec] = (sum(h["downlink_bytes"]), sum(h["uplink_bytes"]))
    assert byts["int8"][0] < 0.6 * byts["none"][0]
    assert byts["int8"][1] < 0.6 * byts["none"][1]
