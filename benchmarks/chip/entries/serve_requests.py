"""The one-chip entry: ``launch.serve.serve_requests``, a synchronous call
that answers a burst of request wires through batched DES, the continuous
batcher and bulk SER."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Tuple


@dataclass
class CallResult:
    t0: float
    t1: float
    responses: List[bytes]
    #: (wire, prompt) -> [(arrival time, token), ...]; streaming entries only
    arrivals: Dict[Tuple[int, int], List[Tuple[float, int]]] = field(default_factory=dict)


class Entry:
    streams = False

    def __init__(self, params, cfg, mix: Dict):
        from repro.launch import serve

        self.serve = serve
        self.params, self.cfg, self.mix = params, cfg, mix

    def call(self, wires: List[bytes]) -> CallResult:
        m = self.mix
        t0 = time.perf_counter()
        out = self.serve.serve_requests(self.params, self.cfg, wires, max_new=m["max_new"],
                                        pad_to=m["pad_to"], slots=m["slots"],
                                        admit_cap=m.get("admit_cap"))
        return CallResult(t0, time.perf_counter(), out)

    def des(self, wires: List[bytes]):
        """The DES layer alone, as the entry calls it."""
        return self.serve.decode_request_batch(wires)

    def ser(self, responses):
        """The SER layer alone: ``[(req_id, [tokens, ...]), ...]`` to wires."""
        return self.serve.encode_response_batch(responses)

    def close(self) -> None:
        self.params = None
