"""KV page shipping: the disaggregated-serving wire plane (ISSUE-14).

Disaggregated prefill/decode serving splits one request across two
worker processes: a PREFILL worker chews the prompt chunk-by-chunk
(compute-bound, bursty) and a DECODE worker runs the token loop
(latency-bound, steady).  The state that has to cross the wire between
them is the lane's finished KV pages — the same gather/re-split
redistribution discipline the elastic checkpoint plane proved for
optimizer state (`parallel/partition.py`, arXiv 2112.01075), applied
live between serving processes at page granularity.

This module owns the WIRE FORMAT only; it is deliberately import-light
(numpy + stdlib, no jax) so both HTTP fronts can parse and verify a
shipment without touching a device:

- `PageExport` — everything a decode worker needs to continue a lane
  exactly where the prefill worker left it: the request contract
  (prompt/max_new/temperature/seed), the committed tokens so far (the
  prefill worker samples the FIRST token — the last prompt token's
  logits produce it, so shipping without it would redo a dispatch), the
  next cache position, and the page stacks `[L, n_pages, ps, H, K]` for
  k and v.
- `serialize_export` / `deserialize_export` — one binary frame: magic,
  length-prefixed JSON header, raw page payload.  The header carries
  the SHA-256 of the payload (checked like checkpoint shards) plus the
  `model_signature` of the exporting pool, so a flipped byte on the
  wire or a mismatched deployment becomes a typed `PageShipError` the
  router answers by RECOMPUTING locally — never silent garbage KV.
- `check_compatible` — the import gate: layer/head/dtype/page-size
  geometry must match bit-for-bit or the pages mean nothing to the
  importing pool.

Sharing is sound for the same reason the radix cache is: KV at
position t is a deterministic function of tokens[0..t] and the
weights, so an installed page holds byte-identical k/v to what the
decode worker would have computed itself — shipped-lane output is
byte-identical to a locally-prefilled lane, greedy or seeded sampling.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import struct
from typing import Dict, List, Optional

import numpy as np

# frame magic + format version: bump WIRE_VERSION on any header/payload
# layout change so a mixed-version fleet fails typed, not misparsed.
# v1: raw k‖v page payload.  v2 (ISSUE-19): adds an optional "quant"
# header section — payload is int8 k‖v followed by the float32
# per-(layer, page, head) scale stacks.  Exact-mode frames still
# serialize as v1 byte-for-byte, so a pre-ISSUE-19 reader keeps working
# until it meets a quantized frame, which it rejects TYPED by version.
MAGIC = b"DL4JKVS\x01"
WIRE_VERSION = 2
_KNOWN_VERSIONS = (1, 2)

# header fields every frame must carry (missing = typed, not KeyError)
_REQUIRED = ("version", "prompt", "max_new", "temperature", "seed",
             "committed", "pos", "page_size", "n_pages", "dtype",
             "shape", "sha256", "model")


class PageShipError(RuntimeError):
    """A KV page shipment could not be accepted: truncated/misframed
    bytes, a failed SHA-256 integrity check, or geometry incompatible
    with the importing pool.  The failure ladder is RECOMPUTE, never
    trust: the router falls back to a local prefill on the decode
    worker (docs/robustness.md "Disaggregated serving")."""


def model_signature(cfg, page_size: int) -> Dict:
    """The geometry a shipped page stack is only meaningful under.
    `max_len`/`vocab_size` ride along for request re-validation on the
    importing side; the KV-shape fields are the hard compatibility
    gate.  A shipped row is `[n_heads, head_dim]` as the pool's one
    layout function gives it (`generation.pool_layout`: full heads, or
    one latent row for all heads); a pool that is not the k and v pair
    says how many arrays it has under `pools`."""
    from deeplearning4j_tpu.parallel.generation import (
        pool_depth,
        pool_layout,
        require_stateless,
    )

    require_stateless(cfg, "page shipping (serving/transfer.py)")
    lay = pool_layout(cfg)
    sig = {"n_layers": int(pool_depth(cfg)), "n_heads": int(lay.heads),
           "head_dim": int(lay.width), "dtype": str(cfg.dtype),
           "max_len": int(cfg.max_len),
           "vocab_size": int(cfg.vocab_size),
           "page_size": int(page_size)}
    if len(lay.names) != 2:
        sig["pools"] = len(lay.names)
    return sig


@dataclasses.dataclass
class PageExport:
    """One lane's shippable state at prefill completion."""

    prompt: List[int]
    max_new: int
    temperature: float
    seed: int
    committed: List[int]        # tokens generated so far (>= 1)
    pos: int                    # next cache position (== len(prompt))
    page_size: int
    pages_k: np.ndarray         # [L, n_pages, ps, heads, width]
    pages_v: Optional[np.ndarray]   # None: a one-pool (latent) export
    model: Dict                 # model_signature of the exporting pool
    session_id: Optional[str] = None
    # admission class (ISSUE-15): rides the frame so a shipped or
    # swapped lane keeps its priority on the pool it lands in; absent
    # in pre-ISSUE-15 frames -> interactive (the historical behavior)
    priority: str = "interactive"
    # billing identity (ISSUE-16): same ride-along contract — a
    # shipped or swapped lane stays charged to its tenant on the pool
    # it lands in; absent in older frames -> the default tenant
    tenant: str = "default"
    # compression (ISSUE-19): when `quant` is set, pages_k/pages_v are
    # int8 and scales_k/scales_v carry the per-(layer, page, head)
    # float32 scales; `quant["exact_dtype"]` remembers what the pages
    # dequantize back to.  None = exact-bytes frame (v1 layout).
    quant: Optional[Dict] = None
    scales_k: Optional[np.ndarray] = None
    scales_v: Optional[np.ndarray] = None

    @property
    def n_pages(self) -> int:
        return int(self.pages_k.shape[1])

    @property
    def stacks(self) -> tuple:
        """The page stacks, one a pool, in the pool's order."""
        return ((self.pages_k,) if self.pages_v is None
                else (self.pages_k, self.pages_v))

    @property
    def scales(self) -> tuple:
        return ((self.scales_k,) if self.pages_v is None
                else (self.scales_k, self.scales_v))

    @property
    def quantized(self) -> bool:
        return self.quant is not None

    def nbytes(self) -> int:
        """Bytes this export actually carries (the at-rest/wire size):
        int8 pages + scales when quantized, raw pages when exact."""
        n = sum(int(st.nbytes) for st in self.stacks)
        if self.scales_k is not None:
            n += sum(int(sc.nbytes) for sc in self.scales)
        return n

    def exact_nbytes(self) -> int:
        """Bytes the same pages occupy un-quantized (the 4x-denominator
        the compression ledger reports against)."""
        if self.quant is None:
            return sum(int(st.nbytes) for st in self.stacks)
        itemsize = np.dtype(self.quant["exact_dtype"]).itemsize
        return int(len(self.stacks) * self.pages_k.size * itemsize)

    def dequantized(self) -> "PageExport":
        """A new exact PageExport with pages restored to
        `quant["exact_dtype"]` (identity when already exact).  Install
        paths call this ONCE at the host boundary so the device install
        program is the same one exact shipments use."""
        if self.quant is None:
            return self
        from deeplearning4j_tpu.precision.quantize import (
            dequantize_kv_pages,
        )

        dt = np.dtype(self.quant["exact_dtype"])
        exact = [dequantize_kv_pages(st, sc, dt)
                 for st, sc in zip(self.stacks, self.scales)]
        return dataclasses.replace(
            self, pages_k=exact[0],
            pages_v=exact[1] if len(exact) > 1 else None,
            quant=None, scales_k=None, scales_v=None)


def quantize_export(ex: PageExport) -> PageExport:
    """Exact PageExport -> per-page int8 quantized PageExport (identity
    when already quantized).  Positions at/past `ex.pos` are zeroed
    before the scales are computed (stale tail-page garbage must not
    crush the live rows' precision — `quantize_kv_pages`)."""
    if ex.quant is not None:
        return ex
    from deeplearning4j_tpu.precision.quantize import quantize_kv_pages

    qk, sk = quantize_kv_pages(ex.pages_k, valid=ex.pos)
    qv, sv = ((None, None) if ex.pages_v is None
              else quantize_kv_pages(ex.pages_v, valid=ex.pos))
    return dataclasses.replace(
        ex, pages_k=qk, pages_v=qv, scales_k=sk, scales_v=sv,
        quant={"mode": "int8", "exact_dtype": str(ex.pages_k.dtype)})


def serialize_export(ex: PageExport) -> bytes:
    """PageExport -> one wire frame: MAGIC + u32 header length + JSON
    header + raw page payload (k then v, C-order; a quantized export
    appends its float32 scale stacks after the int8 pages).  The
    header's sha256 covers the payload bytes exactly as framed.  Exact
    exports frame as v1 — byte-identical to the pre-ISSUE-19 format —
    so quantize-off pools interoperate with old readers unchanged."""
    stacks = [np.ascontiguousarray(st) for st in ex.stacks]
    pk = stacks[0]
    if any(st.shape != pk.shape for st in stacks):
        raise ValueError(f"pages_k {pk.shape} != pages_v "
                         f"{stacks[-1].shape}")
    payload = b"".join(st.tobytes() for st in stacks)
    if ex.quant is not None:
        payload += b"".join(np.ascontiguousarray(sc, np.float32).tobytes()
                            for sc in ex.scales)
    header = {
        "version": WIRE_VERSION if ex.quant is not None else 1,
        "prompt": [int(t) for t in ex.prompt],
        "max_new": int(ex.max_new),
        "temperature": float(ex.temperature),
        "seed": int(ex.seed),
        "committed": [int(t) for t in ex.committed],
        "pos": int(ex.pos),
        "page_size": int(ex.page_size),
        "n_pages": int(pk.shape[1]),
        "dtype": str(pk.dtype),
        "shape": list(pk.shape),
        "sha256": hashlib.sha256(payload).hexdigest(),
        "model": dict(ex.model),
    }
    if ex.quant is not None:
        header["quant"] = {"mode": str(ex.quant["mode"]),
                           "exact_dtype": str(ex.quant["exact_dtype"]),
                           "scale_shape": list(ex.scales_k.shape)}
    if len(stacks) != 2:
        # a one-pool (latent) frame: the payload is one stack, not k then v
        header["pools"] = len(stacks)
    if ex.session_id is not None:
        header["session_id"] = str(ex.session_id)
    if ex.priority != "interactive":
        header["priority"] = str(ex.priority)
    if ex.tenant != "default":
        header["tenant"] = str(ex.tenant)
    hj = json.dumps(header).encode()
    return MAGIC + struct.pack(">I", len(hj)) + hj + payload


def deserialize_export(data: bytes) -> PageExport:
    """One wire frame -> PageExport, integrity-verified.  EVERY malformed
    input — wrong magic, truncated header or payload, non-JSON header,
    missing fields, shape/byte-count mismatch, failed SHA-256 — raises
    `PageShipError` naming what broke, so the import path has exactly
    one failure type to map to its recompute ladder."""
    pre = len(MAGIC) + 4
    if len(data) < pre or data[:len(MAGIC)] != MAGIC:
        raise PageShipError(
            f"not a KV page shipment: bad magic/short frame "
            f"({len(data)} bytes)")
    (hlen,) = struct.unpack(">I", data[len(MAGIC):pre])
    if len(data) < pre + hlen:
        raise PageShipError(
            f"truncated shipment header ({len(data)} bytes, header "
            f"needs {pre + hlen})")
    try:
        header = json.loads(data[pre:pre + hlen])
    except ValueError as e:
        raise PageShipError(f"shipment header is not JSON: {e}") from e
    missing = [k for k in _REQUIRED if k not in header]
    if missing:
        raise PageShipError(f"shipment header missing {missing}")
    if int(header["version"]) not in _KNOWN_VERSIONS:
        raise PageShipError(
            f"shipment wire version {header['version']} not in "
            f"{_KNOWN_VERSIONS}")
    payload = data[pre + hlen:]
    digest = hashlib.sha256(payload).hexdigest()
    if digest != header["sha256"]:
        raise PageShipError(
            f"shipment integrity check failed: sha256 {digest[:12]}… != "
            f"header {str(header['sha256'])[:12]}…")
    shape = tuple(int(d) for d in header["shape"])
    try:
        dt = np.dtype(header["dtype"])
    except TypeError as e:
        raise PageShipError(
            f"shipment dtype {header['dtype']!r} unknown") from e
    quant = header.get("quant")
    sk = sv = None
    if quant is not None:
        if quant.get("mode") != "int8":
            raise PageShipError(
                f"shipment quantization mode {quant.get('mode')!r} "
                f"unknown (this reader speaks int8 only)")
        if dt != np.dtype(np.int8):
            raise PageShipError(
                f"quantized shipment payload dtype {dt} != int8")
        try:
            np.dtype(quant.get("exact_dtype"))
        except TypeError as e:
            raise PageShipError(
                f"shipment exact_dtype {quant.get('exact_dtype')!r} "
                f"unknown") from e
        sshape = tuple(int(d) for d in quant.get("scale_shape", ()))
        if len(sshape) != 3 or sshape[:2] != (shape[0], shape[1]) or \
                sshape[2] != shape[3]:
            raise PageShipError(
                f"shipment scale stack {sshape} != per-(layer, page, "
                f"head) for pages {shape}")
        sbytes = int(np.prod(sshape)) * 4
    else:
        sbytes = 0
    pools = int(header.get("pools", 2))
    if pools not in (1, 2):
        raise PageShipError(f"shipment of {pools} pools: 1 or 2 only")
    half = int(np.prod(shape)) * dt.itemsize
    want = pools * (half + sbytes)
    if len(payload) != want:
        raise PageShipError(
            f"shipment payload {len(payload)} bytes != {want} for "
            f"{pools} x {shape} {dt}"
            + (f" + {pools} x {sshape} float32 scales" if quant else ""))
    pk = np.frombuffer(payload[:half], dt).reshape(shape)
    pv = (np.frombuffer(payload[half:2 * half], dt).reshape(shape)
          if pools == 2 else None)
    if quant is not None:
        at = pools * half
        sk = np.frombuffer(payload[at:at + sbytes], np.float32
                           ).reshape(sshape)
        if pools == 2:
            sv = np.frombuffer(payload[at + sbytes:], np.float32
                               ).reshape(sshape)
        quant = {"mode": "int8",
                 "exact_dtype": str(quant["exact_dtype"])}
    return PageExport(
        prompt=[int(t) for t in header["prompt"]],
        max_new=int(header["max_new"]),
        temperature=float(header["temperature"]),
        seed=int(header["seed"]),
        committed=[int(t) for t in header["committed"]],
        pos=int(header["pos"]),
        page_size=int(header["page_size"]),
        pages_k=pk, pages_v=pv, model=dict(header["model"]),
        session_id=header.get("session_id"),
        priority=str(header.get("priority", "interactive")),
        tenant=str(header.get("tenant", "default")),
        quant=quant, scales_k=sk, scales_v=sv)


def check_compatible(ex: PageExport, cfg, page_size: int,
                     mid_decode: bool = False,
                     prefix: bool = False) -> None:
    """The import gate: shipped geometry must equal the importing
    pool's, field for field — a page stack cut for different
    layers/heads/dtype/page-size would install as silent garbage.
    Raises `PageShipError` naming every mismatched field.

    ``mid_decode`` relaxes the prefill-boundary invariant for the
    overload-survival plane (ISSUE-15): a PREEMPTED lane swaps out
    mid-decode, so its ``pos`` sits anywhere past the prompt — but the
    page-count and committed-token invariants still hold exactly.

    ``prefix`` gates HIBERNATION frames (ISSUE-19): not a live lane but
    a whole-page prompt prefix — ``prompt`` is exactly the covered
    tokens, ``pos`` sits on a page boundary, and ``committed`` is empty
    (nothing was mid-flight; the resuming lane re-runs its own tail)."""
    local = model_signature(cfg, page_size)
    bad = [f"{k}: shipped {ex.model.get(k)!r} != local {v!r}"
           for k, v in local.items() if ex.model.get(k) != v]
    if bad:
        raise PageShipError(
            "shipment incompatible with this pool — " + "; ".join(bad))
    want = (local["n_layers"], ex.n_pages, local["page_size"],
            local["n_heads"], local["head_dim"])
    if tuple(ex.pages_k.shape) != want:
        raise PageShipError(
            f"shipment page stack {tuple(ex.pages_k.shape)} != "
            f"{want} for this pool's geometry")
    if len(ex.stacks) != local.get("pools", 2):
        raise PageShipError(
            f"shipment of {len(ex.stacks)} page stacks for a pool of "
            f"{local.get('pools', 2)} arrays")
    if prefix:
        if ex.pos != len(ex.prompt):
            raise PageShipError(
                f"hibernated prefix pos {ex.pos} != covered tokens "
                f"{len(ex.prompt)}: a prefix frame stores exactly what "
                f"its pages hold")
        if ex.pos % local["page_size"] != 0:
            raise PageShipError(
                f"hibernated prefix pos {ex.pos} is not a multiple of "
                f"page_size {local['page_size']}: only FULL pages rest")
        if ex.committed:
            raise PageShipError(
                f"hibernated prefix carries {len(ex.committed)} "
                f"committed tokens: prefix frames hold pages, not lanes")
    elif mid_decode:
        if ex.pos < len(ex.prompt):
            raise PageShipError(
                f"swapped lane pos {ex.pos} < prompt length "
                f"{len(ex.prompt)}: only post-prefill lanes swap")
    elif ex.pos != len(ex.prompt):
        raise PageShipError(
            f"shipment pos {ex.pos} != prompt length "
            f"{len(ex.prompt)}: only prefill-complete lanes ship")
    if not prefix and not ex.committed:
        raise PageShipError(
            "shipment carries no committed token: prefill completion "
            "always samples the first one")
    if ex.n_pages != -(-ex.pos // local["page_size"]):
        raise PageShipError(
            f"shipment has {ex.n_pages} pages for pos {ex.pos} at "
            f"page_size {local['page_size']}")


__all__ = [
    "MAGIC",
    "PageExport",
    "PageShipError",
    "WIRE_VERSION",
    "check_compatible",
    "deserialize_export",
    "model_signature",
    "quantize_export",
    "serialize_export",
]
