"""Wire-level federated messages: explicit ``Broadcast`` / ``ClientUpdate``
dataclasses with built-in serialized-byte accounting.

The paper's C4 claim is about *communication*: HLoRA transmits exactly what
plain LoRA at each client's rank would, because reconstruction/SVD are
server-side. Before this module, uplink/downlink bytes were an estimate
(``d·r·itemsize`` formulas in bench_comm). Here they are a *measured
property of the wire format*: every message serializes its payload into a
real byte buffer — rank-truncated (only the leading r_k of r_max rank
directions cross the wire) and dtype-aware (bf16 payloads cost 2 bytes per
element, round-tripped exactly via a uint16 view, as in
``checkpoint/store.py``) — and ``num_bytes`` is the length of that buffer.

Wire layout (version ``_WIRE_VERSION``)::

    [4-byte LE header length][header JSON][array buffers, header order]

The header carries the message kind, scalar metadata, and one
``(path, shape, dtype)`` triple per array; buffers are the raw
``ndarray.tobytes()`` payloads concatenated in header order. Round-trip
is exact for every dtype numpy can view (bfloat16 included).

Truncation is lossless by construction: global factors are masked so every
rank direction ≥ r_k is exactly zero, and client gradients cannot flow
into masked directions (``lora.masked_factors``), so slicing ``A[..., :r]``
/ ``B[..., :r, :]`` and zero-padding back reproduces the full-rank arrays
bit-for-bit. Tests pin this (test_session.py).
"""
from __future__ import annotations

import json
import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed.compress import WireCodec, decoder_for

_WIRE_VERSION = 1
_BF16 = "bfloat16"

AdapterPayload = Dict[str, Dict[str, np.ndarray]]   # {target: {"A", "B"}}
HeadPayload = Dict[str, np.ndarray]


# ---------------------------------------------------------------------------
# Low-level pack/unpack
# ---------------------------------------------------------------------------

def _np(x) -> np.ndarray:
    return np.asarray(x)


def _dtype_name(a: np.ndarray) -> str:
    return _BF16 if a.dtype == jnp.bfloat16 else a.dtype.name


def _to_buffer(a: np.ndarray) -> bytes:
    if a.dtype == jnp.bfloat16:
        return np.ascontiguousarray(a).view(np.uint16).tobytes()
    return np.ascontiguousarray(a).tobytes()


def _from_buffer(buf: memoryview, shape, dtype: str) -> np.ndarray:
    if dtype == _BF16:
        return np.frombuffer(buf, np.uint16).view(jnp.bfloat16).reshape(shape)
    return np.frombuffer(buf, np.dtype(dtype)).reshape(shape)


def pack_wire(kind: str, meta: dict, arrays: Dict[str, np.ndarray]) -> bytes:
    """Serialize ``meta`` + named arrays into one contiguous buffer."""
    entries, bufs = [], []
    for path in sorted(arrays):
        a = _np(arrays[path])
        entries.append([path, list(a.shape), _dtype_name(a)])
        bufs.append(_to_buffer(a))
    header = json.dumps({"wire": _WIRE_VERSION, "kind": kind, "meta": meta,
                         "arrays": entries}).encode()
    return struct.pack("<I", len(header)) + header + b"".join(bufs)


def unpack_wire(data: bytes) -> Tuple[str, dict, Dict[str, np.ndarray]]:
    (hlen,) = struct.unpack_from("<I", data, 0)
    header = json.loads(bytes(data[4:4 + hlen]).decode())
    if header["wire"] != _WIRE_VERSION:
        raise ValueError(f"unsupported wire version {header['wire']}")
    arrays, off = {}, 4 + hlen
    view = memoryview(data)
    for path, shape, dtype in header["arrays"]:
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        itemsize = 2 if dtype == _BF16 else np.dtype(dtype).itemsize
        arrays[path] = _from_buffer(view[off:off + n * itemsize], shape,
                                    dtype)
        off += n * itemsize
    return header["kind"], header["meta"], arrays


# ---------------------------------------------------------------------------
# Adapter payload helpers (rank truncation / padding)
# ---------------------------------------------------------------------------

def truncate_adapter(tree, ranks: Dict[str, int]) -> AdapterPayload:
    """Keep only the leading r_t rank directions of each target's factors.

    ``tree`` leaves: A (*stack, d_in, r_max), B (*stack, r_max, d_out).
    SVD components are ordered, so the leading block is the payload; the
    caller guarantees directions ≥ r_t are exactly zero (rank masks).
    """
    out = {}
    for t, ad in tree.items():
        r = int(ranks[t])
        out[t] = {"A": _np(ad["A"])[..., :r],
                  "B": _np(ad["B"])[..., :r, :]}
    return out


class CohortStack:
    """Host arrays of a cohort's decoded payloads, zero-padded back to
    r_max and stacked over the clients, written in place one client at a
    time; the inverse of :func:`truncate_adapter` for a whole cohort.

    The arrays are allocated from the first payload put (zeros, in the
    dtype a device copy of it takes), so each client's rank tail is
    exactly zero and its mask row is ``arange(r_max) < r``. ``tree`` and
    ``heads`` go to the device in one transfer.
    """

    def __init__(self, k: int, r_max: int):
        self.k, self.r_max = int(k), int(r_max)
        self.tree: Dict[str, Dict[str, np.ndarray]] = {}
        self.heads: Dict[str, np.ndarray] = {}
        self._empty = True

    def _allocate(self, adapter: AdapterPayload, head: HeadPayload) -> None:
        k, r = self.k, self.r_max

        def zeros(shape, like):
            return np.zeros(shape, jax.dtypes.canonicalize_dtype(like.dtype))
        for t, ad in adapter.items():
            a, b = _np(ad["A"]), _np(ad["B"])
            self.tree[t] = {
                "A": zeros((k, *a.shape[:-1], r), a),
                "B": zeros((k, *b.shape[:-2], r, b.shape[-1]), b),
                "mask": np.zeros((k, *a.shape[:-2], r), np.float32)}
        for n, v in head.items():
            v = _np(v)
            self.heads[n] = zeros((k, *v.shape), v)
        self._empty = False

    def put(self, i: int, adapter: AdapterPayload,
            head: HeadPayload) -> None:
        """Write client ``i``'s decoded (rank-truncated) payload."""
        if self._empty:
            self._allocate(adapter, head)
        for t, ad in adapter.items():
            a, b = _np(ad["A"]), _np(ad["B"])
            r = a.shape[-1]
            st = self.tree[t]
            st["A"][i, ..., :r] = a
            st["B"][i, ..., :r, :] = b
            st["mask"][i, ..., :r] = 1.0
        for n, v in head.items():
            self.heads[n][i] = v


def pad_adapter(payload: AdapterPayload, r_max: int):
    """Inverse of :func:`truncate_adapter` for one client: factors
    zero-padded back to r_max and the rank mask rebuilt from the
    payload's truncated rank, on the device."""
    stack = CohortStack(1, r_max)
    stack.put(0, payload, {})
    return {t: {leaf: jnp.asarray(v[0]) for leaf, v in ad.items()}
            for t, ad in stack.tree.items()}


def _flatten_payload(adapter: AdapterPayload, head: HeadPayload
                     ) -> Dict[str, np.ndarray]:
    arrays = {}
    for t, ad in adapter.items():
        for leaf, a in ad.items():
            arrays[f"adapter/{t}/{leaf}"] = a
    for k, a in (head or {}).items():
        arrays[f"head/{k}"] = a
    return arrays


def _split_payload(arrays: Dict[str, np.ndarray]
                   ) -> Tuple[AdapterPayload, HeadPayload]:
    adapter: AdapterPayload = {}
    head: HeadPayload = {}
    for path, a in arrays.items():
        parts = path.split("/")
        if parts[0] == "adapter":
            adapter.setdefault(parts[1], {})[parts[2]] = a
        else:
            head[parts[1]] = a
    return adapter, head


def _encode_payload(adapter: AdapterPayload, head: HeadPayload,
                    codec: Optional[WireCodec]
                    ) -> Tuple[Dict[str, np.ndarray], dict]:
    """Flatten (adapter, head) into wire arrays; with a codec the adapter
    crosses the wire encoded (under ``codec/``) plus a self-describing
    header entry. ``codec=None`` is byte-identical to the raw format."""
    if codec is None:
        return _flatten_payload(adapter, head), {}
    enc, cmeta = codec.encode_adapter(adapter)
    arrays = {f"codec/{p}": a for p, a in enc.items()}
    for k, a in (head or {}).items():
        arrays[f"head/{k}"] = a
    return arrays, {"codec": codec.name, "codec_meta": cmeta}


def _decode_payload(arrays: Dict[str, np.ndarray], meta: dict
                    ) -> Tuple[AdapterPayload, HeadPayload]:
    """Inverse of :func:`_encode_payload`, driven purely by the header —
    the receiver needs no codec configuration (self-describing wire)."""
    if "codec" not in meta:
        return _split_payload(arrays)
    enc: Dict[str, np.ndarray] = {}
    head: HeadPayload = {}
    for path, a in arrays.items():
        tag, rest = path.split("/", 1)
        if tag == "codec":
            enc[rest] = a
        else:
            head[rest] = a
    adapter = decoder_for(meta["codec"]).decode_adapter(
        enc, meta["codec_meta"])
    return adapter, head


# ---------------------------------------------------------------------------
# Messages
# ---------------------------------------------------------------------------

@dataclass
class Broadcast:
    """Server → client: rank-truncated global factors + task head.

    ``adapter[t]["A"]``: (*stack, d_in, r_t), ``["B"]``: (*stack, r_t, d_out)
    — r_t = min(r_client, per-target cap), any strategy scale correction
    already applied by the server. ``unpack`` pads back to r_max and
    rebuilds masks, so the client-side tree is bit-identical to the
    server-side masked redistribution.
    """
    version: int
    client_id: int
    adapter: AdapterPayload
    head: HeadPayload = field(default_factory=dict)
    _raw: Optional[bytes] = field(default=None, repr=False, compare=False)
    codec: Optional[WireCodec] = field(default=None, repr=False,
                                       compare=False)

    kind = "broadcast"

    def to_bytes(self) -> bytes:
        if self._raw is None:
            arrays, cmeta = _encode_payload(self.adapter, self.head,
                                            self.codec)
            self._raw = pack_wire(
                self.kind,
                {"version": self.version, "client_id": self.client_id,
                 **cmeta},
                arrays)
        return self._raw

    @classmethod
    def from_bytes(cls, data: bytes) -> "Broadcast":
        kind, meta, arrays = unpack_wire(data)
        if kind != cls.kind:
            raise ValueError(f"expected {cls.kind!r} message, got {kind!r}")
        adapter, head = _decode_payload(arrays, meta)
        return cls(version=meta["version"], client_id=meta["client_id"],
                   adapter=adapter, head=head, _raw=bytes(data))

    @property
    def num_bytes(self) -> int:
        """Measured wire size: the length of the serialized buffer."""
        return len(self.to_bytes())

    def unpack(self, r_max: int):
        """(lora_tree with masks, head) — client-side view at r_max."""
        head = {k: jnp.asarray(v) for k, v in self.head.items()}
        return pad_adapter(self.adapter, r_max), head


@dataclass
class ClientUpdate:
    """Client → server: rank-truncated trained factors + trained head."""
    client_id: int
    start_version: int
    num_examples: int
    adapter: AdapterPayload
    head: HeadPayload = field(default_factory=dict)
    _raw: Optional[bytes] = field(default=None, repr=False, compare=False)
    codec: Optional[WireCodec] = field(default=None, repr=False,
                                       compare=False)

    kind = "update"

    def to_bytes(self) -> bytes:
        if self._raw is None:
            arrays, cmeta = _encode_payload(self.adapter, self.head,
                                            self.codec)
            self._raw = pack_wire(
                self.kind,
                {"client_id": self.client_id,
                 "start_version": self.start_version,
                 "num_examples": self.num_examples,
                 **cmeta},
                arrays)
        return self._raw

    @classmethod
    def from_bytes(cls, data: bytes) -> "ClientUpdate":
        kind, meta, arrays = unpack_wire(data)
        if kind != cls.kind:
            raise ValueError(f"expected {cls.kind!r} message, got {kind!r}")
        adapter, head = _decode_payload(arrays, meta)
        return cls(client_id=meta["client_id"],
                   start_version=meta["start_version"],
                   num_examples=meta["num_examples"],
                   adapter=adapter, head=head, _raw=bytes(data))

    @property
    def num_bytes(self) -> int:
        return len(self.to_bytes())

    def unpack(self, r_max: int):
        head = {k: jnp.asarray(v) for k, v in self.head.items()}
        return pad_adapter(self.adapter, r_max), head


@dataclass
class EdgeAggregate:
    """Edge aggregator → root: one cohort's ``ClientUpdate``s concentrated
    into a single wire message.

    The 'stack' hierarchical mode is *lossless by construction*: the edge
    forwards its clients' serialized updates verbatim (concatenated, with
    per-update lengths in the header), so the root can reassemble the
    exact per-client trees and run the same flat aggregation — this is
    what makes two-tier aggregation bit-identical to flat (tested). The
    'engine' mode ships one pre-merged ``ClientUpdate`` per edge instead;
    that message is the one that actually shrinks edge→root traffic.
    """
    edge_id: int
    updates: List["ClientUpdate"]
    _raw: Optional[bytes] = field(default=None, repr=False, compare=False)

    kind = "edge_aggregate"

    def to_bytes(self) -> bytes:
        if self._raw is None:
            blobs = [u.to_bytes() for u in self.updates]
            blob = np.frombuffer(b"".join(blobs), np.uint8)
            self._raw = pack_wire(
                self.kind,
                {"edge_id": int(self.edge_id),
                 "lengths": [len(b) for b in blobs]},
                {"blob": blob})
        return self._raw

    @classmethod
    def from_bytes(cls, data: bytes) -> "EdgeAggregate":
        kind, meta, arrays = unpack_wire(data)
        if kind != cls.kind:
            raise ValueError(f"expected {cls.kind!r} message, got {kind!r}")
        raw = arrays["blob"].tobytes()
        updates, off = [], 0
        for ln in meta["lengths"]:
            updates.append(ClientUpdate.from_bytes(raw[off:off + ln]))
            off += ln
        return cls(edge_id=meta["edge_id"], updates=updates,
                   _raw=bytes(data))

    @property
    def num_bytes(self) -> int:
        return len(self.to_bytes())


def payload_bytes(msg) -> int:
    """Bytes of array payload alone (excludes the JSON header) — used by
    tests to pin ``num_bytes`` to the actual buffer sizes."""
    arrays = _flatten_payload(msg.adapter, msg.head)
    tot = 0
    for a in arrays.values():
        a = _np(a)
        itemsize = 2 if a.dtype == jnp.bfloat16 else a.dtype.itemsize
        tot += a.size * itemsize
    return tot
