"""Pallas TPU kernel: HGum SER payload pass (token lanes -> phit stream).

Mirror of ``phit_unpack``: pack a run of fixed-width tokens contiguously
into the wire (aligned pitch only — one ``(BLOCK, stride/4)`` word tile per
grid step, flattened to the wire outside the kernel), and assemble framed
streams, stream-fragment rows and the RX header/payload split in batched
row-block passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..fabric.frames import HDR_WORDS
from .phit_unpack import _block_rows, _lane_mask, _round_up


def _copy_kernel(tok_ref, out_ref):
    out_ref[...] = tok_ref[...]


def pack_run(
    tokens: jnp.ndarray,  # (N, nlanes) uint32
    stride: int,  # element pitch in bytes (>= nbytes)
    nbytes: int,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Pack N tokens at pitch `stride` from byte 0; returns u32 wire run.

    Aligned fast path only (stride % 4 == 0); ragged/unaligned encoding goes
    through the jnp oracle (`ref.encode_run_ref`).  Tokens are masked and
    padded to the element pitch first, so the kernel writes whole
    ``(BLOCK, stride/4)`` word tiles and the wire is their row-major view.
    """
    if stride % 4 != 0:
        raise ValueError("pack_run: stride must be 4-byte aligned (use ref path)")
    n, nlanes = tokens.shape
    assert nlanes == (nbytes + 3) // 4
    blk = _block_rows(n)
    cap = _round_up(n, blk)
    stride_w = stride // 4
    toks = jnp.pad(
        tokens & _lane_mask(nbytes, nlanes),
        ((0, cap - n), (0, stride_w - nlanes)),
    )
    out = pl.pallas_call(
        _copy_kernel,
        grid=(cap // blk,),
        in_specs=[pl.BlockSpec((blk, stride_w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk, stride_w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cap, stride_w), jnp.uint32),
        interpret=interpret,
        name="hgum_pack_run",
    )(toks)
    return out.reshape(cap * stride_w)[: n * stride_w]


# ---------------------------------------------------------------------------
# framed streams and stream fragments (HW-to-HW framing, §IV-C)
# ---------------------------------------------------------------------------


def _assemble_kernel(hdr_ref, pay_ref, out_ref):
    # one whole stream (all F frames) per grid step: header phit + payload
    # words concatenate into wire layout lane-parallel across the frames
    out_ref[...] = jnp.concatenate([hdr_ref[...], pay_ref[...]], axis=-1)


def pack_frames_batch(
    headers: jnp.ndarray,  # (B, F, HDR_WORDS) u32 — incl. crc + route words
    payloads: jnp.ndarray,  # (B, F, frame_words) u32 — pre-masked
    *,
    interpret: bool,
) -> jnp.ndarray:
    """Assemble B framed streams (multi-destination send) in one call.

    The structure half (sizes, CRC32, route words, tail masking) comes from
    ``fabric.frames.frame_parts_batch``; this kernel is the payload half —
    one VMEM tile per stream (all of its frames at once, F x width words)
    writes the wire-layout frames, so the grid is B steps rather than the
    old B*F.  Output is (B, F, HDR_WORDS + frame_words), bit-identical to a
    vmapped ``fabric.frames.frame_stream``.
    """
    B, F, frame_words = payloads.shape
    width = HDR_WORDS + frame_words
    return pl.pallas_call(
        _assemble_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, F, HDR_WORDS), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, F, frame_words), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, F, width), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, F, width), jnp.uint32),
        interpret=interpret,
        name="hgum_pack_frames",
    )(headers.astype(jnp.uint32), payloads.astype(jnp.uint32))


def _chunk_kernel(meta_ref, tok_ref, cnt_ref, out_ref):
    # one row block per grid step: [meta | tokens | count] in wire layout
    out_ref[...] = jnp.concatenate(
        [meta_ref[...], tok_ref[...], cnt_ref[...]], axis=-1
    )


def pack_chunks_batch(
    meta: jnp.ndarray,  # (B, 3) u32 — stream_id, step, flags per chunk
    tokens: jnp.ndarray,  # (B, capW) u32 — pre-masked element words
    counts: jnp.ndarray,  # (B, 1) u32 — true element count per chunk
    *,
    block: int = 8,
    interpret: bool,
) -> jnp.ndarray:
    """Assemble B small stream fragments into wire rows in one call.

    The streaming plane emits ONE tiny fragment per live sequence per
    decode tick; batching them through a single Pallas pass amortizes the
    SER launch the same way ``pack_frames_batch`` does for whole messages.
    Output rows are ``[stream_id, step, flags, w0..w_{capW-1}, count]``
    — the HW->SW List layout (count AFTER elements, §IV-B), so rows
    trimmed to their live element words concatenate into a burst the host
    parses back-to-front.  The kernel is width-generic: ``capW`` is
    ``cap * elem_words`` for whatever element width the ``Stream<T>``
    plan generated (see ``core.stream_plans``), and the trailing count
    stays the element count.
    """
    B, cap = tokens.shape
    width = cap + meta.shape[1] + 1
    capB = -(-max(B, 1) // block) * block
    padB = capB - B
    meta = jnp.pad(meta.astype(jnp.uint32), ((0, padB), (0, 0)))
    tokens = jnp.pad(tokens.astype(jnp.uint32), ((0, padB), (0, 0)))
    counts = jnp.pad(counts.astype(jnp.uint32), ((0, padB), (0, 0)))
    out = pl.pallas_call(
        _chunk_kernel,
        grid=(capB // block,),
        in_specs=[
            pl.BlockSpec((block, meta.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((block, cap), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((capB, width), jnp.uint32),
        interpret=interpret,
        name="hgum_pack_chunks",
    )(meta, tokens, counts)
    return out[:B]


def _split_kernel(fr_ref, hdr_ref, pay_ref):
    fr = fr_ref[...]
    hdr_ref[...] = fr[:, :HDR_WORDS]
    pay_ref[...] = fr[:, HDR_WORDS:]


def unpack_frames_batch(
    frames: jnp.ndarray,  # (N, HDR_WORDS + frame_words) u32
    *,
    block: int = 8,
    interpret: bool,
) -> tuple:
    """Split a batch of received frames into (headers, payloads).

    The RX-side twin of ``pack_frames_batch``: (N, width) delivered frames
    -> headers (N, HDR_WORDS) and payload words (N, frame_words), one row
    block per grid step.
    """
    N, width = frames.shape
    frame_words = width - HDR_WORDS
    cap = -(-max(N, 1) // block) * block
    fr = jnp.pad(frames.astype(jnp.uint32), ((0, cap - N), (0, 0)))
    hdr, pay = pl.pallas_call(
        _split_kernel,
        grid=(cap // block,),
        in_specs=[pl.BlockSpec((block, width), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((block, HDR_WORDS), lambda i: (i, 0)),
            pl.BlockSpec((block, frame_words), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((cap, HDR_WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((cap, frame_words), jnp.uint32),
        ),
        interpret=interpret,
        name="hgum_unpack_frames",
    )(fr)
    return hdr[:N], pay[:N]
