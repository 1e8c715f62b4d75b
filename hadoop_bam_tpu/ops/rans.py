"""Batched device rANS 4x8 decode (CRAM block method 4).

The TPU mapping of the reference stack's entropy decoder (SURVEY.md
section 2.8 row 5: htsjdk/htslib rANS reached through CRAM decode).
An rANS stream is serial *within* a block — the four interleaved 32-bit
states share one renormalization byte stream, so each step's byte
consumption depends on all previous steps — but blocks are independent.
The device decode therefore vectorizes ACROSS blocks: a ``lax.scan`` over
output steps whose body decodes 4 states x B blocks of lanes on the VPU,
with table lookups as batched gathers.

Per step and state: ``m = x & 0xFFF; s = slot2sym[m];
x' = freq[s] * (x >> 12) + m - cum[s]``, then at most two 8-bit
renormalization reads (``x >= freq >= 1`` after a step gives
``x' >= 2^11``, and two byte loads reach ``>= 2^27 > 2^23``) [SPEC
CRAMcodecs rANS].  Order-0 interleaves states over positions
(state j owns positions 4k + j); order-1 gives each state one quarter of
the output with per-context tables keyed on the previous byte.

Host side (table parsing, padding, batch assembly) reuses
formats/cram_codecs.py — the same tables drive the NumPy, native C++,
and device decoders, so parity tests pin all three to each other.

Backend selection: ``rans_decode_batch(payloads, backend=...)`` with
"host" (native C++/NumPy per stream — the throughput default),
"device" (this module), or "auto" (host: one stream at a time on the
host has the lower latency).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hadoop_bam_tpu.formats.cram_codecs import (
    RANS_LOW, RANS_ORDER_0, RANS_ORDER_1, RansError, TF_SHIFT, TOTFREQ,
    normalize_truncation, rans4x8_decode, read_order0_tables,
    read_order1_tables,
)

_MASK = TOTFREQ - 1


def _round_pow2(x: int, lo: int = 1) -> int:
    n = lo
    while n < x:
        n <<= 1
    return n


# ---------------------------------------------------------------------------
# Device kernels (jnp + lax.scan; vectorized over the block axis)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("steps",))
def _decode0_batch(data, states0, ptr0, freqs, cums, slot2sym, n_out,
                   steps: int):
    """Order-0 batch: data [B, L] u8 (padded), states0 [B, 4] u32,
    ptr0 [B] i32, freqs/cums [B, 256] u32, slot2sym [B, 4096] u8,
    n_out [B] i32 -> [B, 4 * steps] u8 (positions past n_out are junk)."""
    def gather(tbl, idx):
        return jnp.take_along_axis(tbl, idx[:, None].astype(jnp.int32),
                                   axis=1)[:, 0]

    def body(carry, step):
        states, ptr = carry
        outs = []
        for j in range(4):
            x = states[:, j]
            active = (4 * step + j) < n_out
            m = x & jnp.uint32(_MASK)
            sym = gather(slot2sym, m).astype(jnp.uint32)
            f = gather(freqs, sym)
            c = gather(cums, sym)
            x2 = f * (x >> TF_SHIFT) + m - c
            for _ in range(2):  # renorm: at most two byte reads
                need = x2 < jnp.uint32(RANS_LOW)
                byte = gather(data, ptr).astype(jnp.uint32)
                x2 = jnp.where(need, (x2 << 8) | byte, x2)
                ptr = ptr + jnp.where(active & need, 1, 0)
            states = states.at[:, j].set(jnp.where(active, x2, x))
            outs.append(sym.astype(jnp.uint8))
        return (states, ptr), jnp.stack(outs, axis=1)   # [B, 4]

    (fstates, fptr), ys = jax.lax.scan(body, (states0, ptr0),
                                       jnp.arange(steps, dtype=jnp.int32))
    return (jnp.transpose(ys, (1, 0, 2)).reshape(ys.shape[1], -1),
            fstates, fptr)


@functools.partial(jax.jit, static_argnames=("steps",))
def _decode1_batch(data, states0, ptr0, freqs, cums, slot2sym, q, rem,
                   steps: int):
    """Order-1 batch: freqs/cums [B, 256*256] u32 (ctx-major), slot2sym
    [B, 256*4096] u8, q/rem [B] i32 -> [B, 4, steps] u8 (state-major;
    state j holds quarter j, state 3 also the tail remainder)."""
    def gather(tbl, idx):
        return jnp.take_along_axis(tbl, idx[:, None].astype(jnp.int32),
                                   axis=1)[:, 0]

    def body(carry, step):
        states, ptr, ctxs = carry
        outs = []
        for j in range(4):
            x = states[:, j]
            lens_j = q + (rem if j == 3 else 0)
            active = step < lens_j
            m = x & jnp.uint32(_MASK)
            ctx = ctxs[:, j]
            sym = gather(slot2sym,
                         ctx * TOTFREQ + m.astype(jnp.int32)
                         ).astype(jnp.uint32)
            f = gather(freqs, ctx * 256 + sym.astype(jnp.int32))
            c = gather(cums, ctx * 256 + sym.astype(jnp.int32))
            x2 = f * (x >> TF_SHIFT) + m - c
            for _ in range(2):
                need = x2 < jnp.uint32(RANS_LOW)
                byte = gather(data, ptr).astype(jnp.uint32)
                x2 = jnp.where(need, (x2 << 8) | byte, x2)
                ptr = ptr + jnp.where(active & need, 1, 0)
            states = states.at[:, j].set(jnp.where(active, x2, x))
            ctxs = ctxs.at[:, j].set(
                jnp.where(active, sym.astype(jnp.int32), ctx))
            outs.append(sym.astype(jnp.uint8))
        return (states, ptr, ctxs), jnp.stack(outs, axis=1)

    ctxs0 = jnp.zeros_like(states0, dtype=jnp.int32)
    (fstates, fptr, _), ys = jax.lax.scan(body, (states0, ptr0, ctxs0),
                                          jnp.arange(steps, dtype=jnp.int32))
    return jnp.transpose(ys, (1, 2, 0)), fstates, fptr  # [B, 4, steps]


# ---------------------------------------------------------------------------
# Host batch assembly
# ---------------------------------------------------------------------------

def _parse_header(payload: bytes) -> Tuple[int, int, int]:
    if len(payload) < 9:
        raise RansError("rANS stream shorter than its 9-byte prefix")
    order = payload[0]
    comp_size = int.from_bytes(payload[1:5], "little")
    out_size = int.from_bytes(payload[5:9], "little")
    if len(payload) < 9 + comp_size:
        raise RansError("truncated rANS stream")
    return order, comp_size, out_size


def _pad_batch(blocks: Sequence[Tuple[np.ndarray, np.ndarray, int, int]],
               b_cap: int):
    """(body u8, states u32[4], body_pos, out_size) list -> padded arrays.

    Shapes round up (B to b_cap, lengths to pow2) so jit caches stay
    small across batches."""
    B = len(blocks)
    max_body = _round_pow2(max(b.size for b, *_ in blocks) + 8, 64)
    data = np.zeros((b_cap, max_body), dtype=np.uint8)
    states = np.zeros((b_cap, 4), dtype=np.uint32)
    ptr = np.zeros(b_cap, dtype=np.int32)
    n_out = np.zeros(b_cap, dtype=np.int32)
    # dummy rows keep states >= RANS_LOW so the renorm loop never loops
    states[:, :] = RANS_LOW
    for i, (body, st, pos, osz) in enumerate(blocks):
        data[i, :body.size] = body
        states[i] = st
        ptr[i] = pos
        n_out[i] = osz
    return data, states, ptr, n_out, B


def _check_final(fstates: np.ndarray, fptr: np.ndarray, chunk) -> None:
    """Integrity check after a batched device decode.

    The encoder initializes every state to RANS_LOW, so a well-formed
    stream decodes back to exactly RANS_LOW with the shared byte pointer
    landing on the end of the renorm bytes.  A corrupt/truncated payload
    (whose out-of-range gathers clamp silently under JAX semantics) fails
    one of the two — raise instead of returning garbage, matching the
    host decoder's error behavior.  ``chunk`` is the [(payload index,
    block)] list so errors name the batch-level payload, not the
    chunk-local row."""
    for k, (i, (body, _st, _pos, _osz)) in enumerate(chunk):
        if fptr[k] != body.size or (fstates[k] != RANS_LOW).any():
            raise RansError(
                f"device rANS decode integrity failure on payload {i}: "
                f"consumed {int(fptr[k])}/{body.size} renorm bytes, "
                f"final states {fstates[k].tolist()} (want all "
                f"{RANS_LOW}) — corrupt or truncated stream")


def rans_decode_batch_device(payloads: Sequence[bytes]) -> List[bytes]:
    """Decode many rANS 4x8 streams on the default JAX device, batched.

    Parity oracle: formats/cram_codecs.rans4x8_decode per stream."""
    results: List[Optional[bytes]] = [None] * len(payloads)
    o0: List[Tuple[int, tuple]] = []    # (payload idx, parsed block)
    o1: List[Tuple[int, tuple]] = []
    tables0: List[Tuple[np.ndarray, np.ndarray]] = []
    tables1: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []

    for i, p in enumerate(payloads):
        order, comp_size, out_size = _parse_header(p)
        if out_size == 0:
            results[i] = b""
            continue
        body = np.frombuffer(p, dtype=np.uint8, count=comp_size, offset=9)
        with normalize_truncation(f"rANS (payload {i})"):
            if order == RANS_ORDER_0:
                freqs, cum, slot2sym, pos = read_order0_tables(p, 9)
                tables0.append((freqs, cum[:256], slot2sym))
            elif order == RANS_ORDER_1:
                freqs, cums, slot2sym, pos = read_order1_tables(p, 9)
                tables1.append((freqs, cums[:, :256], slot2sym))
            else:
                raise RansError(f"unknown rANS order {order}")
            if len(p) < pos + 16:
                raise RansError("truncated rANS stream (state words)")
            st = np.frombuffer(p[pos:pos + 16], dtype="<u4").copy()
            (o0 if order == RANS_ORDER_0 else o1).append(
                (i, (body[pos - 9 + 16:], st, 0, out_size)))

    # --- order-0: vectorize across up to 256 blocks per dispatch
    CH0 = 256
    for lo in range(0, len(o0), CH0):
        chunk = o0[lo:lo + CH0]
        tabs = tables0[lo:lo + CH0]
        b_cap = _round_pow2(len(chunk), 8)
        data, states, ptr, n_out, B = _pad_batch(
            [blk for _, blk in chunk], b_cap)
        freqs = np.zeros((b_cap, 256), dtype=np.uint32)
        cums = np.zeros((b_cap, 256), dtype=np.uint32)
        slot = np.zeros((b_cap, TOTFREQ), dtype=np.uint8)
        for k, (f, c, s) in enumerate(tabs):
            freqs[k], cums[k], slot[k] = f, c, s
        freqs[B:, :] = 1  # dummy rows: nonzero freq keeps states sane
        steps = _round_pow2((int(n_out.max()) + 3) // 4)
        out, fstates, fptr = _decode0_batch(
            jnp.asarray(data), jnp.asarray(states), jnp.asarray(ptr),
            jnp.asarray(freqs), jnp.asarray(cums), jnp.asarray(slot),
            jnp.asarray(n_out), steps)
        out = np.asarray(out)
        _check_final(np.asarray(fstates), np.asarray(fptr), chunk)
        for k, (i, (_b, _s, _p, osz)) in enumerate(chunk):
            results[i] = out[k, :osz].tobytes()

    # --- order-1: larger tables, smaller chunks
    CH1 = 16
    for lo in range(0, len(o1), CH1):
        chunk = o1[lo:lo + CH1]
        tabs = tables1[lo:lo + CH1]
        b_cap = _round_pow2(len(chunk), 4)
        data, states, ptr, n_out, B = _pad_batch(
            [blk for _, blk in chunk], b_cap)
        freqs = np.zeros((b_cap, 256 * 256), dtype=np.uint32)
        cums = np.zeros((b_cap, 256 * 256), dtype=np.uint32)
        slot = np.zeros((b_cap, 256 * TOTFREQ), dtype=np.uint8)
        for k, (f, c, s) in enumerate(tabs):
            freqs[k] = f.reshape(-1)
            cums[k] = c.reshape(-1)
            slot[k] = s.reshape(-1)
        freqs[B:, :] = 1
        q = n_out >> 2
        rem = n_out - 3 * q - q
        steps = _round_pow2(int((q + rem).max()))
        out, fstates, fptr = _decode1_batch(
            jnp.asarray(data), jnp.asarray(states), jnp.asarray(ptr),
            jnp.asarray(freqs), jnp.asarray(cums), jnp.asarray(slot),
            jnp.asarray(q), jnp.asarray(rem), steps)    # [B, 4, steps]
        out = np.asarray(out)
        _check_final(np.asarray(fstates), np.asarray(fptr), chunk)
        for k, (i, (_b, _s, _p, osz)) in enumerate(chunk):
            qq, rr = osz >> 2, osz - 4 * (osz >> 2)
            parts = [out[k, 0, :qq], out[k, 1, :qq], out[k, 2, :qq],
                     out[k, 3, :qq + rr]]
            results[i] = np.concatenate(parts).tobytes()

    return results  # type: ignore[return-value]


def rans_decode_batch(payloads: Sequence[bytes],
                      backend: str = "auto") -> List[bytes]:
    """Decode a batch of rANS 4x8 streams.

    backend="host": native C++/NumPy, stream at a time (default under
    "auto" — single-stream latency wins on the host).  backend="device":
    the batched VPU decode above."""
    if backend == "device":
        return rans_decode_batch_device(payloads)
    return [rans4x8_decode(p) for p in payloads]
