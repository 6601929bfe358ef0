"""The streaming entry: ``launch.serve.serve_requests_streaming`` on a
rank-0 ingress and ``n_shards`` serving shards over the routed fabric, ARQ on
(the serve default).  The fabric is built once, in set-up, and every call
reuses it.  Tokens are timed as they reach the ingress, through
``on_token``."""
from __future__ import annotations

import time
from typing import Dict, List

from .serve_requests import CallResult, Entry as _Batched


class Entry(_Batched):
    streams = True

    def __init__(self, params, cfg, mix: Dict):
        super().__init__(params, cfg, mix)
        self.fabric = self.serve.default_serve_fabric(n_shards=mix["n_shards"], arq=True)
        if self.fabric is None or self.fabric.n_ranks != mix["n_shards"] + 1:
            raise RuntimeError(f"no fabric of {mix['n_shards'] + 1} ranks")

    def call(self, wires: List[bytes]) -> CallResult:
        m = self.mix
        arrivals: Dict = {}
        clock = time.perf_counter

        def on_token(req, prompt, step, tok):
            arrivals.setdefault((req, prompt), []).append((clock(), int(tok)))

        t0 = clock()
        out = self.serve.serve_requests_streaming(
            self.params, self.cfg, wires, max_new=m["max_new"], pad_to=m["pad_to"],
            slots=m["slots"], admit_cap=m.get("admit_cap"), fabric=self.fabric,
            on_token=on_token)
        return CallResult(t0, clock(), out, arrivals)

    def close(self) -> None:
        super().close()
        self.fabric = None
