"""Jitted public wrappers around the HGum Pallas kernels.

``decode_runs`` is the production DES payload pass: it takes the wire plus a
*run table* (the structure pass output — one row per uniform run of a leaf
field) and returns the unpacked token lanes for each requested leaf.

Every wrapper takes ``interpret=None``, which :func:`resolve_interpret`
turns into the Pallas interpreter on the CPU backend and a compiled Mosaic
kernel on any other: on a TPU a kernel compiles or raises, and never falls
back to the interpreter.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.vectorized import BatchedDecodePlan, DecodePlan, stack_wires
from ..fabric.frames import frame_parts_batch
from .frame_pack import (
    pack_chunks_batch,
    pack_frames_batch,
    pack_run,
    unpack_frames_batch,
)
from .phit_unpack import unpack_gather, unpack_run


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret only where the default backend is the CPU."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def wire_to_u32(wire: bytes | np.ndarray) -> jnp.ndarray:
    """bytes -> little-endian uint32 lanes (tail zero-padded)."""
    buf = np.frombuffer(wire, np.uint8) if isinstance(wire, bytes) else np.asarray(wire, np.uint8)
    pad = (-len(buf)) % 4
    if pad:
        buf = np.concatenate([buf, np.zeros(pad, np.uint8)])
    return jnp.asarray(buf.view(np.uint32))


@functools.partial(jax.jit, static_argnames=("base", "stride", "count", "nbytes", "interpret"))
def decode_run(wire_u32, base: int, stride: int, count: int, nbytes: int,
               interpret: Optional[bool] = None):
    return unpack_run(wire_u32, base, stride, count, nbytes,
                      interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("nbytes", "interpret"))
def decode_gather(wire_u32, offsets, nbytes: int, interpret: Optional[bool] = None):
    return unpack_gather(wire_u32, offsets, nbytes,
                         interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("stride", "nbytes", "interpret"))
def encode_run(tokens, stride: int, nbytes: int, interpret: Optional[bool] = None):
    return pack_run(tokens, stride, nbytes, interpret=resolve_interpret(interpret))


@functools.partial(
    jax.jit,
    static_argnames=("list_level", "frame_phits", "interpret", "adaptive"),
)
def encode_frames_batch(
    payloads_u32,  # (B, Wcap) u32 — one row per send, zero-padded
    nbytes,  # (B,) int32 true byte lengths
    routes,  # (B, 3) int32 (src, dst, seq0) per stream
    list_level: int = 1,
    frame_phits: int = 16,
    interpret: Optional[bool] = None,
    adaptive: bool = False,  # stamp the shortest-path route-word bit
):
    """Multi-destination SER: B wires -> B routed framed streams.

    One vectorized structure pass (headers: sizes, CRC32, route words) plus
    one Pallas assembly pass.  Returns (frames (B, F, width), n_frames (B,)).
    """
    hdr, data, n_frames = frame_parts_batch(
        payloads_u32, nbytes, routes, list_level=list_level,
        frame_phits=frame_phits, adaptive=adaptive,
    )
    frames = pack_frames_batch(hdr, data, interpret=resolve_interpret(interpret))
    return frames, n_frames


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_frames_batch(frames_u32, interpret: Optional[bool] = None):
    """RX split of delivered frames: (N, width) -> (headers, payloads)."""
    return unpack_frames_batch(frames_u32, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("elem_words", "interpret"))
def encode_chunks_batch(
    meta,  # (B, 3) int32/u32 — (stream_id, step, flags) per chunk
    tokens,  # (B, cap*elem_words) element words, zero-padded past each count
    counts,  # (B,) int32 true ELEMENT counts
    elem_words: int = 1,
    interpret: Optional[bool] = None,
):
    """Generated stream-fragment SER: B fragments -> B wire rows
    ``[meta | element words | count]`` (count after elements, §IV-B).

    This is the Pallas pack path driven by ``core.stream_plans``: the
    plan's static ``elem_words`` (u32 words per element — 1 for the
    classic ``Stream<Bytes 4>`` token chunks) scales the tail mask, and
    the trailing count word stays the *element* count so bursts parse
    back-to-front regardless of element width.  Tail words beyond each
    fragment's ``count * elem_words`` are masked to zero here, then the
    Pallas ``pack_chunks_batch`` kernel assembles every row in one pass.
    """
    counts = jnp.asarray(counts, jnp.uint32)
    col = jnp.arange(tokens.shape[1], dtype=jnp.uint32)[None, :]
    nwords = counts[:, None] * jnp.uint32(elem_words)
    toks = jnp.where(col < nwords, tokens.astype(jnp.uint32), 0)
    return pack_chunks_batch(
        jnp.asarray(meta), toks, counts[:, None],
        interpret=resolve_interpret(interpret),
    )


# ---------------------------------------------------------------------------
# Plan-driven decode: choose run-kernel vs gather-kernel per leaf
# ---------------------------------------------------------------------------


def runs_from_plan(plan: DecodePlan, path: str) -> Optional[Tuple[int, int]]:
    """If `path`'s instances form one uniform run, return (base, stride)."""
    n = plan.counts[path]
    if n == 0:
        return None
    offs = np.asarray(plan.offsets[path][:n])
    if n == 1:
        return int(offs[0]), max(plan.nbytes[path], 4)
    strides = np.diff(offs)
    if np.all(strides == strides[0]) and strides[0] > 0:
        return int(offs[0]), int(strides[0])
    return None


def wires_to_u32(wires: List[bytes]) -> Tuple[jnp.ndarray, int]:
    """Stack N wires into one flat u32 lane buffer.

    Rows are padded to a common 4-byte-aligned length L so per-message byte
    offsets become flat offsets by adding ``m * L``.  Returns (lanes, L).
    """
    L = -(-max([len(w) for w in wires] + [1]) // 4) * 4
    mat = stack_wires(wires, pad_to=L)
    return jnp.asarray(mat.reshape(-1).view(np.uint32)), L


def batched_runs_from_plan(
    bplan: BatchedDecodePlan, path: str, row_bytes: int
) -> Optional[Tuple[int, int]]:
    """If `path` is one uniform run in EVERY message at the same (base,
    stride) relative to its row, the flat batch is itself a uniform run of
    ``N * cap`` instances (stride between rows = row_bytes).  This is the
    fixed-layout fast path (e.g. batch_schema rows): one ``unpack_run``
    covers the whole serving batch."""
    n = bplan.counts[path]
    cap = bplan.cap(path)
    if not np.all(n == cap) or cap == 0:
        return None  # ragged: padding rows would break the run
    offs = np.asarray(bplan.offsets[path])
    if cap == 1:
        # one instance per row: consecutive flat instances sit exactly one
        # row apart, so the row itself is the stride
        stride = row_bytes
    else:
        strides = np.diff(offs, axis=1)
        if not (np.all(strides == strides[0, 0]) and strides[0, 0] > 0):
            return None
        stride = int(strides[0, 0])
    if not np.all(offs[:, 0] == offs[0, 0]):
        return None
    # flat offset of (msg m, inst k) is base + m*row_bytes + k*stride; this
    # equals base + (m*cap + k)*stride — one big run — iff cap*stride tiles
    # the row exactly.
    if cap * stride != row_bytes:
        return None
    return int(offs[0, 0]), stride


def decode_batch_kernel(
    wires_u32: jnp.ndarray,  # flat lanes from wires_to_u32
    row_bytes: int,
    bplan: BatchedDecodePlan,
    paths: Optional[List[str]] = None,
    interpret: Optional[bool] = None,
) -> Dict[str, jnp.ndarray]:
    """Batched DES payload pass on the Pallas kernels.

    ONE ``unpack_run``/``unpack_gather`` call per leaf path decodes that leaf
    for every message in the batch (this is the kernel twin of
    ``repro.core.vectorized.decode_batch``).  Returns
    path -> uint32[N, cap, nlanes].
    """
    N = bplan.n_messages
    base = (np.arange(N, dtype=np.int64) * row_bytes)[:, None]
    out = {}
    for p in paths or bplan.offsets.keys():
        nbytes = bplan.nbytes[p]
        cap = bplan.cap(p)
        run = batched_runs_from_plan(bplan, p, row_bytes)
        if run is not None:
            b, stride = run
            lanes = decode_run(
                wires_u32, b, stride, N * cap, nbytes, interpret=interpret
            )
        else:
            offs = jnp.asarray(
                (bplan.offsets[p] + base).reshape(-1).astype(np.int32)
            )
            lanes = decode_gather(wires_u32, offs, nbytes, interpret=interpret)
        out[p] = lanes.reshape(N, cap, lanes.shape[-1])
    return out


def decode_message_kernel(
    wire_u32: jnp.ndarray,
    plan: DecodePlan,
    paths: Optional[List[str]] = None,
    interpret: Optional[bool] = None,
) -> Dict[str, jnp.ndarray]:
    """DES payload pass using the Pallas kernels (run fast-path per leaf)."""
    out = {}
    for p in paths or plan.offsets.keys():
        nbytes = plan.nbytes[p]
        run = runs_from_plan(plan, p)
        if run is not None:
            base, stride = run
            got = decode_run(
                wire_u32, base, stride, plan.counts[p], nbytes, interpret=interpret
            )
            cap = plan.cap(p)
            if got.shape[0] < cap:
                got = jnp.pad(got, ((0, cap - got.shape[0]), (0, 0)))
            out[p] = got
        else:
            out[p] = decode_gather(
                wire_u32, jnp.asarray(plan.offsets[p]), nbytes, interpret=interpret
            )
    return out
