"""HGum Pallas TPU kernels (DES/SER payload pass).

``phit_unpack`` / ``frame_pack`` are the tiled production kernels with
explicit BlockSpec VMEM tiling; ``ops`` holds the jitted wrappers;
``ref`` the pure-jnp oracles the tests assert against.
"""
from .ops import (
    batched_runs_from_plan,
    decode_batch_kernel,
    decode_frames_batch,
    decode_gather,
    decode_message_kernel,
    decode_run,
    encode_chunks_batch,
    encode_frames_batch,
    encode_run,
    resolve_interpret,
    runs_from_plan,
    wire_to_u32,
    wires_to_u32,
)

__all__ = [
    "batched_runs_from_plan", "decode_batch_kernel", "decode_frames_batch",
    "decode_gather", "decode_message_kernel", "decode_run",
    "encode_chunks_batch", "encode_frames_batch", "encode_run",
    "resolve_interpret", "runs_from_plan", "wire_to_u32", "wires_to_u32",
]
