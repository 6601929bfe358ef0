"""Typed inter-device channels: the HW-to-HW direction on TPU (DESIGN.md §3).

A *framed channel* moves a variable-length byte stream (a List in HGum
terms) between mesh neighbours as fixed-size frames with ``(size,
ListLevel)`` headers — the paper's §IV-C protocol verbatim, carried by
``jax.lax.ppermute`` over the ICI instead of an FPGA link.  An empty frame
terminates the list; a real CRC32 word (IEEE 802.3, zlib-compatible —
see ``repro.fabric.frames``) extends the header for fault detection.

The framing/checksum core is SHARED with the routed fabric
(``repro.fabric``): this module keeps the seed's single-hop API
(``frame_stream`` / ``unframe_stream`` / ``pod_ring_exchange``) as the
point-to-point special case, re-exported from one implementation so the
wire format cannot drift between the neighbour channel and the multi-hop
router.  For arbitrary-rank delivery use ``repro.fabric.Fabric``.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, PartitionSpec as P

# One wire format, one implementation: the fabric owns framing + CRC32.
from ..fabric.frames import (  # noqa: F401  (re-exported public API)
    FRAME_PHITS,
    HDR_WORDS,
    PHIT_WORDS,
    crc32_words,
    frame_stream,
    unframe_stream,
)

__all__ = [
    "FRAME_PHITS", "HDR_WORDS", "PHIT_WORDS", "crc32_words",
    "frame_stream", "unframe_stream", "pod_ring_exchange",
    "make_framed_sender",
]


# ---------------------------------------------------------------------------
# Framed ring exchange over a mesh axis (pod<->pod, stage<->stage)
# ---------------------------------------------------------------------------


def pod_ring_exchange(
    frames: jax.Array, axis_name: str, shift: int = 1
) -> jax.Array:
    """ppermute a framed stream one hop around `axis_name` (call under
    shard_map).  The framed stream is self-describing, so the receiver can
    decode without out-of-band length metadata — the paper's point."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(frames, axis_name, perm)


def make_framed_sender(mesh: Mesh, axis_name: str, frame_phits: int = FRAME_PHITS):
    """shard_map-wrapped send along `axis_name`.

    Takes per-member payloads stacked on dim 0: payload (n, W) u32 and
    nbytes (n,) — both sharded over `axis_name` — and returns the rotated
    (payload, nbytes, ok) with the same layout.  The framed stream is
    self-describing, so no out-of-band length metadata crosses the link.
    """

    def send(payload_u32, nbytes):
        frames, _ = frame_stream(
            payload_u32[0], nbytes[0], frame_phits=frame_phits
        )
        out = pod_ring_exchange(frames, axis_name)
        p, nb, ok = unframe_stream(out)
        return p[None], nb[None], ok[None]

    return jax.shard_map(
        send,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name), P(axis_name)),
        check_vma=False,
    )
