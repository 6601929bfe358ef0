"""Request and response wires, written and read without the program.

The client side of the message plane, kept apart from the code under test so
that a fault in the program's own SER or DES cannot hide behind a matching
fault here.  The formats are those of ``request_schema`` and
``response_schema`` (HGum, paper §IV):

* request, software to hardware: ``req_id`` u64, then the prompt count u32,
  then per prompt its token count u32 followed by the tokens, u32 each;
* response, hardware to software: ``req_id`` u64, then per output its tokens
  followed by their count, then the output count u32 last, so that the host
  parses from the end.

Everything is little-endian.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

U32 = np.dtype("<u4")


def encode_request(req_id: int, prompts: Sequence[np.ndarray]) -> bytes:
    parts = [int(req_id).to_bytes(8, "little"),
             np.array([len(prompts)], U32).tobytes()]
    for p in prompts:
        parts.append(np.array([len(p)], U32).tobytes())
        parts.append(np.asarray(p, U32).tobytes())
    return b"".join(parts)


def decode_response(wire: bytes) -> Tuple[int, List[List[int]]]:
    """(req_id, outputs) of one response wire; raises ValueError on a wire
    that does not parse to its last byte."""
    if len(wire) < 12:
        raise ValueError(f"response wire of {len(wire)} bytes is too short")
    pos = len(wire) - 4
    n_out = int(np.frombuffer(wire, U32, 1, pos)[0])
    outs: List[List[int]] = []
    for _ in range(n_out):
        if pos - 4 < 8:
            raise ValueError("response wire ends inside its outputs")
        pos -= 4
        count = int(np.frombuffer(wire, U32, 1, pos)[0])
        start = pos - 4 * count
        if start < 8:
            raise ValueError(f"output of {count} tokens overruns the wire")
        outs.append(np.frombuffer(wire, U32, count, start).astype(np.int64).tolist())
        pos = start
    if pos != 8:
        raise ValueError(f"response wire has {pos - 8} bytes left before its outputs")
    outs.reverse()
    return int.from_bytes(wire[:8], "little"), outs
