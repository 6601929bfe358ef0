"""Multi-hop frame router over the JAX device mesh.

The seed's ``pod_ring_exchange`` moves frames exactly one hop between ring
neighbours; every multi-device pattern had to be hand-wired out of single
hops.  This module generalizes it to a packet-switched fabric in the spirit
of "Framework for Application Mapping over Packet-Switched Network of
FPGAs": frames carry a ``(src, dst, seq)`` route word (``frames.py``) and a
:class:`Router` delivers them to arbitrary ranks by composing
``jax.lax.ppermute`` steps.

Topology and algorithm
----------------------
* Ranks are the row-major flattening of the mesh coordinates along
  ``axis_names`` (so a ``(4, 2)`` x/y mesh has ``rank = x*2 + y``).
* **Dimension-ordered routing**: frames first travel along the first axis
  until their destination coordinate on that axis matches, then along the
  next axis, and so on — deadlock-free and deterministic, the standard
  mesh/torus discipline.
* **Shortest-path direction choice** (``config.routing = "shortest"``, the
  default): on each axis a frame whose +1 distance exceeds half the ring
  takes the -1 direction instead, so the worst case halves from ``n - 1``
  hops to ``n // 2``.  Every scan step moves BOTH directions (two
  ``ppermute``s over disjoint link buffers), each direction with its own
  ``credits`` budget and its own QoS weighted-round-robin pass — a
  bidirectional ring has twice the link capacity of the +1 ring, and the
  scheduler treats each physical direction as the independent link it is.
  The choice is per *frame*: the route word's adaptive bit (``frames.py``)
  gates it, so legacy +1-only frames and shortest-path frames coexist in
  one tick.  ``routing = "dimension"`` keeps the PR-2/PR-3 +1-ring
  discipline bit-for-bit.
* **Credit-based flow control**: each directed link carries at most
  ``config.credits`` frames per step (the paper's bounded-BRAM
  back-pressure analog).  Frames that cannot be injected wait in a
  per-device queue; transiting frames have priority over fresh injections,
  which preserves per-source FIFO order along a path.
* **Congestion-aware direction defection** (``config.defect_after = k``,
  default 0 = off): every device tracks, per (outgoing link, direction), how
  many *consecutive* scan steps that link's credit budget left eligible
  demand waiting.  A queued frame whose route word carries the adaptive bit
  may *defect* to the opposite ring direction once its preferred link has
  been starved for ``k`` straight steps — but only into that direction's
  *spare* credits (after its natural traffic was scheduled), so at most
  ``credits`` frames defect per step and a starved queue cannot stampede
  onto the other ring.  A defector commits to its new direction for the
  rest of the axis (the commitment travels with the frame through the
  ppermutes), which bounds its path at ``n - 1`` hops and rules out
  ping-pong oscillation.  Defection changes *paths*, never bytes: the
  receiver reorders frames by ``seq``, so delivery stays byte-identical to
  static shortest-path and dimension-order routing (property-tested).
* **Early-exit scans** (``config.early_exit``, default on): each axis scan
  runs as a ``lax.while_loop`` that stops as soon as no device still holds
  a frame needing the axis (one cheap global ``psum`` of a bool per step),
  with the static per-axis bound as the cap.  The demand bound therefore
  prices the *worst case* while the tick pays only for the traffic it
  actually carries — in particular the conservative defection bound (a
  defector may ride the long way around) costs nothing when nothing
  defects.
* **QoS credit classes** (``config.qos_weights``): instead of handing the
  per-link credits to the frontmost frames FIFO, the inject step can run
  *weighted round-robin* over credit classes keyed by the frame's
  ``ListLevel`` (``class = level % n_classes``).  Each class holds a static
  quota of the link credits (largest-remainder split of the weights) and
  unused quota spills to the other classes in queue order, so the scheduler
  stays work-conserving.  ``deliver`` additionally reports the scan step at
  which every frame arrived (``rx_step``), which makes in-tick queueing
  delay — and therefore starvation — observable to the mailbox layer.
* Every step is one ``ppermute`` per active direction of a
  ``(credits, width)`` link buffer inside a ``lax.scan``; the step count is
  a static worst-case bound (pipeline fill + frames over the busiest
  possible link), so the whole delivery jits to one XLA program with no
  host round-trips.  :meth:`Router.plan_steps` tightens the bound from the
  tick's *actual* demand (per-ring directed link loads and true hop
  distances) and reports which directions each axis really uses, so a
  one-destination burst does not pay for the all-to-all worst case — and an
  axis nobody crosses costs zero scan steps.

Two delivery entry points:

* :meth:`Router.deliver` — takes already-framed ``(ranks, T, width)`` TX
  buffers (the PR-2/PR-3 three-program path; ``mailbox.py`` frames on a
  separate jit and scatters on host).
* :meth:`Router.deliver_fused` — the whole tick as ONE jitted program:
  batched framing (structure pass + Pallas assembly), device-side scatter
  into per-rank TX rows, the routed scan, and the Pallas RX split all fuse
  into a single ``jax.jit``, so frames never bounce through host memory
  between the three stages.

The router works on *stacked* buffers — ``tx`` is ``(ranks, T, width)``
sharded over the mesh axes — matching the repo's shard_map test idiom.
Higher-level message semantics (reassembly, per-message corruption flags)
live in ``mailbox.py``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .frames import (
    HDR_LEVEL,
    HDR_WORDS,
    PHIT_WORDS,
    route_adaptive,
    route_dst,
    route_src,
    verify_frames,
)

#: on-device counter-block layout (import-pure, so no cycle): the scan
#: carry accumulates one int32 vector per device and returns it alongside
#: the delivered frames — the fused no-host-sync path stays sync-free.
#: The attribution layout (``ATT_*``/``n_att``) is the per-FRAME flight
#: recorder: those columns ride WITH each frame through the link-buffer
#: ppermutes instead of aggregating per device.
from ..obs.counters import (
    ATT_DEFECT,
    ATT_ENTER,
    ATT_STALL,
    ATT_WAIT,
    N_ATT_FIXED,
    ctr_index,
    global_index,
    n_att,
    n_counters,
)

#: shared validation rules — the static analyzer and the runtime raise the
#: SAME messages (repro.analysis.rules is fabric-free at import time)
from ..analysis.findings import Severity
from ..analysis.rules import fabric_config_findings, max_ranks_error

#: direction masks for plan_steps / the per-axis scan builder, shared with
#: the analyzer's communication pass (defined there before any import, so
#: this line is cycle-safe whichever package loads first)
from ..analysis.comm import DIR_BWD, DIR_FWD


@dataclass(frozen=True)
class FabricConfig:
    """Knobs of the routed fabric."""

    frame_phits: int = 16  # payload phits per frame
    credits: int = 4  # max in-flight frames per directed link per step
    rx_frames: Optional[int] = None  # per-rank delivery capacity (default R*T)
    #: weighted round-robin credit classes at the inject step, keyed by
    #: ``ListLevel % len(qos_weights)``.  None = single-class FIFO (legacy).
    qos_weights: Optional[Tuple[int, ...]] = None
    #: "shortest" = per-frame direction choice (go -1 when it is the shorter
    #: way around the ring); "dimension" = the legacy +1-only discipline.
    routing: str = "shortest"
    #: run the tick as one fused jit (pack -> route -> RX split) instead of
    #: three programs with host syncs between them.  The three-program path
    #: remains for fault injection (``Fabric.tx_hook``) and as the
    #: regression oracle.
    fused: bool = True
    #: congestion-aware direction defection: an adaptive frame whose
    #: preferred link has been credit-starved for this many CONSECUTIVE
    #: scan steps may take the opposite ring direction instead (into that
    #: direction's spare credits only).  0 = off — the static per-frame
    #: shortest-path choice of PR 4, bit-for-bit.
    defect_after: int = 0
    #: stop each axis scan as soon as no device still holds a frame that
    #: needs the axis (one global psum of a bool per step); the static
    #: demand bound becomes a cap instead of the price every tick pays.
    early_exit: bool = True
    #: ARQ reliability layer (``mailbox.py``): senders keep sent messages
    #: in a bounded per-(src, dst) retransmit buffer keyed by the route
    #: word's seq; receivers turn CRC failures and seq gaps into compact
    #: NACK / cumulative-ACK control frames riding QoS class
    #: ``arq_level``; senders retransmit on NACK or on a tick-count
    #: timeout with capped exponential backoff.  Off by default — the
    #: detection-only (flag-and-deliver) behavior of PRs 2-8, bit for
    #: bit.  The serve plane opts in (``default_serve_fabric``).
    arq: bool = False
    #: ticks without an ACK before a sender retransmits unprompted
    #: (doubles per retry, capped at 32x)
    retransmit_timeout: int = 8
    #: retransmits per message before the sender gives up and dead-letters
    #: it (0 = a single NACK/timeout aborts immediately)
    max_retries: int = 4
    #: retransmit-buffer bound per (src, dst) stream, in FRAMES — must
    #: stay under SEQ_MOD // 2 or cumulative ACKs turn ambiguous
    #: (rule ``fabric-arq-window``)
    arq_buffer: int = 1024
    #: ListLevel the ACK/NACK control frames ride (reserved: user sends
    #: at this level are rejected while arq is on) — under qos_weights it
    #: maps to credit class ``arq_level % n_classes``, which must earn a
    #: nonzero quota (rule ``fabric-arq-control-class``)
    arq_level: int = 255
    #: receiver give-up horizon: after this many ticks stuck on one seq
    #: gap, flag the partial message and resync past it.  0 = derive from
    #: the retransmit schedule (timeout * (max_retries + 2))
    arq_skip_after: int = 0
    #: receiver ACK cadence: cumulative-ACK every Nth tick that delivered
    #: in-order frames (1 = every tick; coalescing keeps control traffic
    #: sublinear in message rate)
    arq_ack_every: int = 2

    def __post_init__(self) -> None:
        # the analyzer's fabric pass is the single source of these checks
        # (repro.analysis.rules): construction raises the first ERROR
        # finding's message verbatim, so the error a user hits here and
        # the finding `python -m repro.analysis` reports are identical.
        for f in fabric_config_findings(
            self.frame_phits, self.credits, self.routing,
            self.defect_after, self.qos_weights,
            arq=self.arq, retransmit_timeout=self.retransmit_timeout,
            max_retries=self.max_retries, arq_buffer=self.arq_buffer,
            arq_level=self.arq_level, arq_skip_after=self.arq_skip_after,
        ):
            if f.severity is Severity.ERROR:
                raise ValueError(f.message)

    @property
    def skip_after(self) -> int:
        """Effective receiver give-up horizon (resolves the 0 default
        from the retransmit schedule: every retry must have had a chance
        to arrive before the receiver resyncs past the gap)."""
        if self.arq_skip_after > 0:
            return self.arq_skip_after
        return self.retransmit_timeout * (self.max_retries + 2)

    @property
    def frame_width(self) -> int:
        return HDR_WORDS + self.frame_phits * PHIT_WORDS

    @property
    def adaptive(self) -> bool:
        return self.routing == "shortest"

    @property
    def defection(self) -> bool:
        """Congestion-aware defection active (adaptive routing + k > 0)."""
        return self.adaptive and self.defect_after > 0


def qos_quotas(credits: int, weights: Sequence[int]) -> Tuple[int, ...]:
    """Largest-remainder split of the link credits across credit classes.

    Every class gets >= 1 credit (guaranteed feasible by the config check
    ``credits >= len(weights)``) and the quotas sum to exactly ``credits``,
    so the per-step link capacity is unchanged by QoS.
    """
    w = np.asarray(weights, np.float64)
    raw = credits * w / w.sum()
    q = np.maximum(np.floor(raw).astype(np.int64), 1)
    while q.sum() > credits:  # trim overflow from the largest class
        q[int(np.argmax(q))] -= 1
    rem = raw - np.floor(raw)
    while q.sum() < credits:  # hand slack to the largest remainders
        i = int(np.argmax(rem))
        q[i] += 1
        rem[i] -= 1.0
    return tuple(int(x) for x in q)


def _compact_to(valid: jnp.ndarray, cap: int, *cols):
    """Stable partition: scatter valid rows (order-preserving) to the front
    of fresh ``cap``-row buffers.  One cumsum + one scatter per column —
    O(n), replacing the old O(n log n) argsort — and rows past ``cap`` are
    dropped (reported via the overflow flag) instead of silently kept.
    Returns (valid', cols', overflow)."""
    pos = jnp.where(valid, jnp.cumsum(valid) - 1, cap)
    out_valid = jnp.zeros((cap,), bool).at[pos].set(valid, mode="drop")
    outs = tuple(
        jnp.zeros((cap,) + c.shape[1:], c.dtype).at[pos].set(c, mode="drop")
        for c in cols
    )
    overflow = jnp.sum(valid) > cap
    return out_valid, outs, overflow


def _append(rx, rx_cnt, rx_step, rx_att, ok, frames, take, step_no, att):
    """Append ``frames[take]`` rows to the rx buffer at ``rx_cnt``, recording
    the scan step each row arrived at and its attribution vector."""
    rx_cap = rx.shape[0]
    pos = jnp.where(take, rx_cnt + jnp.cumsum(take) - 1, rx_cap)
    rx = rx.at[pos].set(frames, mode="drop")
    rx_step = rx_step.at[pos].set(step_no, mode="drop")
    rx_att = rx_att.at[pos].set(att, mode="drop")
    new_cnt = rx_cnt + jnp.sum(take)
    ok = ok & (new_cnt <= rx_cap)
    return rx, jnp.minimum(new_cnt, rx_cap), rx_step, rx_att, ok


class Router:
    """Routed delivery of framed streams between arbitrary mesh ranks."""

    def __init__(
        self,
        mesh: Mesh,
        axis_names: Optional[Sequence[str]] = None,
        config: FabricConfig = FabricConfig(),
    ):
        self.mesh = mesh
        self.axis_names = tuple(axis_names or mesh.axis_names)
        self.sizes = tuple(mesh.shape[a] for a in self.axis_names)
        self.n_ranks = math.prod(self.sizes)
        err = max_ranks_error(self.n_ranks)
        if err is not None:  # same rule (and words) as Fabric.__init__
            raise ValueError(err)
        self.config = config
        self._jitted = {}
        self._fused = {}

    # -- coordinate helpers (row-major rank <-> per-axis coords) ----------

    def _stride(self, ai: int) -> int:
        return math.prod(self.sizes[ai + 1 :])

    def _coord(self, rank: jnp.ndarray, ai: int) -> jnp.ndarray:
        return (rank // self._stride(ai)) % self.sizes[ai]

    def _coord_int(self, rank: int, ai: int) -> int:
        return (rank // self._stride(ai)) % self.sizes[ai]

    def hops(self, src: int, dst: int) -> int:
        """Total +1-ring (dimension-order) hops from src to dst.

        Pure host integer math — ``place_requests`` calls this per request,
        so it must not build device arrays or force a sync.
        """
        return sum(
            (self._coord_int(dst, ai) - self._coord_int(src, ai)) % n
            for ai, n in enumerate(self.sizes)
        )

    def min_hops(self, src: int, dst: int) -> int:
        """Total hops under shortest-path routing (per-axis min of the two
        ring directions) — what a ``routing="shortest"`` frame traverses."""
        total = 0
        for ai, n in enumerate(self.sizes):
            d = (self._coord_int(dst, ai) - self._coord_int(src, ai)) % n
            total += min(d, n - d)
        return total

    def route_hops(self, src: int, dst: int) -> int:
        """Hops under THIS router's configured routing mode (placement must
        rank shards by the distance frames actually travel)."""
        if self.config.adaptive:
            return self.min_hops(src, dst)
        return self.hops(src, dst)

    # -- demand-aware scan bounds -----------------------------------------

    def default_steps(self, total: int) -> Tuple[Tuple[int, int], ...]:
        """Worst-case per-axis (steps, dirs): every live frame crosses the
        busiest link and needs the full pipeline fill.  Shortest-path halves
        the fill term (max hops per axis drop from ``n`` to ``n // 2``);
        with defection enabled a starved frame may wait ``defect_after``
        steps and then ride the long way around (up to ``n - 1`` hops), so
        the fill term grows back to ``n + defect_after`` — early-exit scans
        make the looser cap free whenever nothing actually defects."""
        credits = self.config.credits
        out = []
        for n in self.sizes:
            if n == 1:
                out.append((0, 0))
                continue
            if self.config.defection:
                fill, dirs = n + self.config.defect_after, DIR_FWD | DIR_BWD
            elif self.config.adaptive:
                fill, dirs = n // 2, DIR_FWD | DIR_BWD
            else:
                fill, dirs = n, DIR_FWD
            out.append((-(-total // credits) + fill + 1, dirs))
        return tuple(out)

    def plan_steps(
        self,
        srcs: Sequence[int],
        dsts: Sequence[int],
        counts: Sequence[int],
    ) -> Tuple[Tuple[int, int], ...]:
        """Per-axis (scan steps, direction mask) from the tick's ACTUAL
        demand — pure host numpy, no device work.

        Frames route dimension-ordered, so while a frame crosses axis ``ai``
        its other coordinates are pinned (axes before ``ai`` already at the
        destination, axes after still at the source); that tuple names the
        physical ring the frame rides.  Frames on different rings — or
        moving in opposite directions on one ring — never compete for a
        link, so the busiest-contention-set bound is per (ring, direction):
        ``ceil(group_frames / credits) + group_max_hops + 1``.  The result
        is never looser than :meth:`default_steps` and is rounded up to an
        even step count so nearby traffic shapes share a jit cache entry.
        An axis no frame crosses costs 0 steps (skipped entirely), and a
        direction no frame takes skips its ppermute.

        With **defection** enabled, a ring whose load exceeds the per-step
        credit budget can starve frames into the opposite direction, so for
        those rings the two direction groups merge: the bound becomes
        ``ceil(ring_load / credits) + (n - 1) + defect_after + 1`` (the
        preferred link always drains >= ``credits``/step — defectors only
        ever consume the other direction's *spare* credits — and a defector
        rides at most ``n - 1`` hops after waiting ``defect_after`` steps),
        and both directions keep their ppermutes.  Rings that can never
        starve (``load <= credits``) keep the tight per-direction bound.
        The early-exit scan makes the slack free when nothing defects.
        """
        # the load matrix + bounds live in the analyzer's communication
        # pass (lazy import: those functions are defined after the module
        # cycle re-entry point), so the matrix `python -m repro.analysis`
        # reports and the bounds this router jits from cannot disagree.
        from ..analysis.comm import bounds_from_loads, demand_link_loads

        defect = self.config.defect_after if self.config.defection else 0
        loads = demand_link_loads(
            self.sizes, srcs, dsts, counts, self.config.adaptive
        )
        return bounds_from_loads(
            loads, self.sizes, self.config.credits, defect,
            self.default_steps(sum(counts)),
        )

    # -- delivery ----------------------------------------------------------

    def deliver(
        self,
        tx: jnp.ndarray,
        tx_valid: jnp.ndarray,
        total_frames: Optional[int] = None,
    ) -> Tuple[jnp.ndarray, ...]:
        """Route every valid tx frame to its destination rank.

        ``tx`` is ``(ranks, T, width)`` u32 (width = HDR + payload words),
        ``tx_valid`` ``(ranks, T)`` bool.  ``total_frames`` is an optional
        upper bound on valid frames across all ranks (default ``R*T``): the
        scan length derives from it, so a tight bound means fewer hop steps.
        Returns ``(rx, rx_count, ok, crc_ok, rx_step, rx_att, counters)``:
        delivered frames per rank in arrival order, the per-rank count, a
        routing flag (False on undeliverable frames or buffer overflow —
        both indicate a misconfigured fabric), a CRC flag (False when a
        delivered frame fails its checksum), the scan step each frame
        arrived at (in-tick queueing latency: self-sends arrive at step 0,
        each ppermute hop or credit stall adds one), the per-frame
        attribution block (``repro.obs.counters`` ``ATT_*`` layout — the
        flight recorder: ``wait + stall + sum(transit) == rx_step``
        exactly, per frame), and the per-rank telemetry counter block
        (``repro.obs.counters`` layout), all accumulated device-side
        inside the scan.
        """
        R, T, W = tx.shape
        if R != self.n_ranks or W != self.config.frame_width:
            raise ValueError(
                f"tx shape {tx.shape} vs ranks={self.n_ranks}, "
                f"width={self.config.frame_width}"
            )
        total = self.bucket_total(total_frames, T)
        key = (T, total)
        fn = self._jitted.get(key)
        if fn is None:
            fn = self._jitted[key] = self._build(T, total)
        return fn(tx, tx_valid)

    def bucket_total(self, total_frames: Optional[int], T: int) -> int:
        """Pow2-bucket the live-frame bound so the jit cache is reused
        across ticks (idempotent: feeding a bucketed value back is a
        no-op — the Mailbox memoizes on exactly this value)."""
        R = self.n_ranks
        total = min(total_frames or R * T, R * T)
        if total < R * T:
            total = min(1 << max(total - 1, 0).bit_length(), R * T)
        return total

    def _capacities(self, T: int, total: int) -> Tuple[int, int]:
        """(rx_cap, q_cap) for a tick of ``total`` live frames and per-rank
        TX depth ``T`` — ONE derivation shared by the fused and
        three-program builders, so the two paths always agree on queue/RX
        sizing (the bit-identity regression tests rely on that)."""
        cfg = self.config
        rx_cap = cfg.rx_frames or min(self.n_ranks * T, total)
        arrivals = cfg.credits * (2 if cfg.adaptive else 1)
        return rx_cap, max(total, T) + arrivals

    def _build(self, T: int, total: int):
        axis_steps = self.default_steps(total)
        rx_cap, q_cap = self._capacities(T, total)
        local = self._build_local(T, axis_steps, q_cap, rx_cap)
        spec = P(self.axis_names)
        return jax.jit(
            jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(spec, spec),
                out_specs=(spec,) * 7,
                check_vma=False,
            )
        )

    def _build_local(
        self,
        T: int,
        axis_steps: Tuple[Tuple[int, int], ...],
        q_cap: int,
        rx_cap: int,
    ):
        """The per-device routing program: inject/hop/deliver scan per axis.

        ``axis_steps`` is a static (steps, direction-mask) per axis —
        ``plan_steps`` output for demand-tight ticks, ``default_steps`` for
        the worst case.  A 0-step axis is skipped entirely; a direction
        absent from the mask skips its ppermute.
        """
        cfg = self.config
        W = cfg.frame_width
        credits = cfg.credits
        axes = self.axis_names
        quotas = (
            qos_quotas(credits, cfg.qos_weights) if cfg.qos_weights else None
        )

        def select(levels, elig):
            """Pick one direction's link occupants: FIFO, or weighted
            round-robin over ListLevel credit classes (work-conserving —
            quota a class leaves unused spills to the others).  Also
            returns the number of frames admitted via the spill (the
            ``link.spilled`` telemetry counter — 0 in FIFO mode, where no
            class quotas exist to spill)."""
            if quotas is None:
                return elig & (jnp.cumsum(elig) <= credits), jnp.int32(0)
            cls = levels.astype(jnp.int32) % len(quotas)
            take = jnp.zeros_like(elig)
            for c, qc in enumerate(quotas):
                in_c = elig & (cls == c)
                take = take | (in_c & (jnp.cumsum(in_c) <= qc))
            rest = elig & ~take
            spill = credits - jnp.sum(take)
            spilled = rest & (jnp.cumsum(rest) <= spill)
            return take | spilled, jnp.sum(spilled, dtype=jnp.int32)

        K = n_att(len(axes))

        def hop(queue, take, axis, perm, att, extra=None):
            """Scatter this direction's occupants into the link buffer and
            move it one hop.  The valid flag, the per-frame attribution
            vector, and — with defection — the direction commitment ride
            as trailing u32 columns of the SAME buffer, so each direction
            costs exactly ONE ppermute per step regardless of how much
            per-frame state travels with the frames."""
            E = 2 if extra is not None else 1
            pos = jnp.where(take, jnp.cumsum(take) - 1, credits)
            buf = jnp.pad(queue, ((0, 0), (0, E + K)))
            buf = buf.at[:, W].set(take.astype(jnp.uint32))
            if extra is not None:
                buf = buf.at[:, W + 1].set(extra.astype(jnp.uint32))
            buf = buf.at[:, W + E:].set(att.astype(jnp.uint32))
            link = jnp.zeros((credits, W + E + K), jnp.uint32).at[pos].set(
                buf, mode="drop"
            )
            arr = jax.lax.ppermute(link, axis, perm)
            avalid = arr[:, W] != 0
            adir = arr[:, W + 1].astype(jnp.int32) if extra is not None else None
            aatt = arr[:, W + E:].astype(jnp.int32)
            return arr[:, :W], avalid, adir, aatt

        NC = n_counters(len(axes))
        IDX_DELIVERED = global_index(len(axes), "delivered")
        IDX_CRC_FAIL = global_index(len(axes), "crc_fail")

        def local(tx, tx_valid):  # (1, T, W), (1, T) — one device's view
            coords = [jax.lax.axis_index(a) for a in axes]
            me = sum(
                c * self._stride(ai) for ai, c in enumerate(coords)
            ).astype(jnp.int32)

            pad = q_cap - T
            queue = jnp.pad(tx[0], ((0, pad), (0, 0)))
            qvalid = jnp.pad(tx_valid[0], (0, pad))
            rx = jnp.zeros((rx_cap, W), jnp.uint32)
            rx_cnt = jnp.int32(0)
            rx_step = jnp.zeros((rx_cap,), jnp.int32)
            ok = jnp.array(True)
            step_no = jnp.int32(0)
            # telemetry counter block (obs.counters layout), accumulated
            # device-side alongside the routing itself.  Every field is an
            # order-independent EVENT count (sums of takes, anys of demand)
            # so the fused and three-program paths — whose queue layouts
            # and static scan bounds differ — agree bit-for-bit.
            ctr = jnp.zeros((NC,), jnp.int32)
            # per-frame flight recorder: one attribution vector per queue
            # row, updated once per EXECUTED scan step.  At every step a
            # live queued frame lands in exactly one of {hopped, stalled,
            # waiting}, so per frame `wait + stall + sum(transit)` counts
            # every step from 1 to its arrival — i.e. equals rx_step
            # exactly, on either engine (the step schedules are identical
            # under the default early-exit scans).
            qatt = jnp.zeros((q_cap, K), jnp.int32)
            rx_att = jnp.zeros((rx_cap, K), jnp.int32)

            # self-sends never cross a link: deliver them up front (step 0,
            # all attribution components zero)
            self_take = qvalid & (route_dst(queue) == me)
            rx, rx_cnt, rx_step, rx_att, ok = _append(
                rx, rx_cnt, rx_step, rx_att, ok, queue, self_take, step_no,
                qatt,
            )
            ctr = ctr.at[IDX_DELIVERED].add(
                jnp.sum(self_take, dtype=jnp.int32)
            )
            qvalid = qvalid & ~self_take

            for ai, axis in enumerate(axes):
                n_axis = self.sizes[ai]
                steps, dirs = axis_steps[ai]
                if n_axis == 1 or steps == 0:
                    continue
                fwd_perm = [(i, (i + 1) % n_axis) for i in range(n_axis)]
                bwd_perm = [(i, (i - 1) % n_axis) for i in range(n_axis)]
                myc = coords[ai]
                half = n_axis // 2
                use_fwd = bool(dirs & DIR_FWD)
                use_bwd = bool(dirs & DIR_BWD)
                # defection needs both ppermutes live on the axis (plan_steps
                # only emits a one-direction mask when no ring can starve)
                defect = cfg.defect_after if (
                    cfg.defection and use_fwd and use_bwd
                ) else 0
                # hoisted: the per-frame scheduling keys (destination coord
                # on this axis, ListLevel class, adaptive flag) are computed
                # ONCE for the resident queue and only for the <= arrivals
                # rows each step, instead of re-derived for all q_cap rows
                # every step.
                qdst = self._coord(route_dst(queue), ai).astype(jnp.int32)
                qlvl = queue[:, HDR_LEVEL]
                qadp = route_adaptive(queue)
                # source coordinate on this axis: a frame's FIRST hop on
                # the axis happens on the device still at that coordinate,
                # which is how `link.entered` counts each frame exactly
                # once per axis (the observed demand_link_loads fold).
                qsrc = self._coord(route_src(queue), ai).astype(jnp.int32)
                ix_f = {
                    f: ctr_index(ai, 0, f)
                    for f in ("entered", "forwarded", "starved",
                              "defect_out", "spare_in", "spilled",
                              "occupied")
                }
                ix_b = {f: ctr_index(ai, 1, f) for f in ix_f}

                def step(carry, ai=ai, axis=axis, n_axis=n_axis,
                         myc=myc, half=half, use_fwd=use_fwd,
                         use_bwd=use_bwd, fwd_perm=fwd_perm,
                         bwd_perm=bwd_perm, defect=defect,
                         ix_f=ix_f, ix_b=ix_b):
                    # new carry state (qsrc, ctr, qatt, rx_att) rides at
                    # the END of the tuple so `more_of`'s positional reads
                    # stay valid
                    if defect:
                        (queue, qdst, qlvl, qadp, qdir, qvalid,
                         rx, rx_cnt, rx_step, ok, step_no, sf, sb,
                         qsrc, ctr, qatt, rx_att) = carry
                    else:
                        (queue, qdst, qlvl, qadp, qvalid,
                         rx, rx_cnt, rx_step, ok, step_no,
                         qsrc, ctr, qatt, rx_att) = carry
                    step_no = step_no + 1

                    def count(take):
                        return jnp.sum(take, dtype=jnp.int32)
                    # inject: frames still off-coordinate on this axis, up
                    # to `credits` per direction per step, scheduled by
                    # `select` (transit priority comes from arrivals being
                    # re-queued at the front below)
                    fwd = (qdst - myc) % n_axis
                    elig = qvalid & (fwd != 0)
                    prefer_bwd = qadp & (fwd > half) if use_bwd else (
                        jnp.zeros_like(elig)
                    )
                    if defect:
                        # a committed defector keeps its direction for the
                        # rest of the axis; everyone else uses the static
                        # shortest-path preference
                        go_bwd = jnp.where(qdir == 0, prefer_bwd, qdir == 2)
                    else:
                        go_bwd = prefer_bwd
                    take_f, spill_f = (
                        select(qlvl, elig & ~go_bwd) if use_fwd
                        else (None, None)
                    )
                    take_b, spill_b = (
                        select(qlvl, elig & go_bwd) if use_bwd
                        else (None, None)
                    )
                    if defect:
                        # per-(link, direction) starvation: demand this
                        # direction's credits left waiting THIS step
                        starved_f = jnp.any(elig & ~go_bwd & ~take_f)
                        starved_b = jnp.any(elig & go_bwd & ~take_b)
                        # defectors: uncommitted adaptive frames whose
                        # preferred link has starved `defect` straight
                        # steps, admitted only into the OPPOSITE
                        # direction's spare credits (after its natural
                        # traffic) — at most `credits` defect per step, so
                        # a starved queue cannot stampede onto the other
                        # ring and re-congest it
                        can_b = (elig & ~go_bwd & ~take_f & qadp
                                 & (qdir == 0) & (sf >= defect))
                        extra_b = can_b & (
                            jnp.cumsum(can_b) <= credits - jnp.sum(take_b)
                        )
                        can_f = (elig & go_bwd & ~take_b & qadp
                                 & (qdir == 0) & (sb >= defect))
                        extra_f = can_f & (
                            jnp.cumsum(can_f) <= credits - jnp.sum(take_f)
                        )
                        take_f = take_f | extra_f
                        take_b = take_b | extra_b
                        # commitment travels with the frame (hopped below)
                        qdir = jnp.where(
                            extra_b, 2, jnp.where(extra_f, 1, qdir)
                        ).astype(jnp.int32)
                        sf = jnp.where(starved_f, sf + 1, 0)
                        sb = jnp.where(starved_b, sb + 1, 0)
                        # a defector leaves its preferred direction
                        # (defect_out) and consumes the opposite one's
                        # spare credits (spare_in): globally the two sum
                        # to the same total
                        ctr = ctr.at[ix_f["defect_out"]].add(count(extra_b))
                        ctr = ctr.at[ix_b["spare_in"]].add(count(extra_b))
                        ctr = ctr.at[ix_b["defect_out"]].add(count(extra_f))
                        ctr = ctr.at[ix_f["spare_in"]].add(count(extra_f))
                    # per-(direction) telemetry — all pure event counts
                    # over demand and takes, so identical whatever static
                    # scan bound or queue layout produced them: `entered`
                    # only at a frame's first hop on the axis (device
                    # coordinate still equals the frame's source
                    # coordinate), `occupied`/`starved` as per-step demand
                    # booleans (steps with no eligible demand add 0, which
                    # is what keeps differing scan bounds invisible).
                    if use_fwd:
                        el_f = elig & ~go_bwd
                        ctr = ctr.at[ix_f["entered"]].add(
                            count(take_f & (qsrc == myc)))
                        ctr = ctr.at[ix_f["forwarded"]].add(count(take_f))
                        ctr = ctr.at[ix_f["spilled"]].add(spill_f)
                        ctr = ctr.at[ix_f["occupied"]].add(
                            jnp.any(el_f).astype(jnp.int32))
                        ctr = ctr.at[ix_f["starved"]].add(
                            jnp.any(el_f & ~take_f).astype(jnp.int32))
                    if use_bwd:
                        el_b = elig & go_bwd
                        ctr = ctr.at[ix_b["entered"]].add(
                            count(take_b & (qsrc == myc)))
                        ctr = ctr.at[ix_b["forwarded"]].add(count(take_b))
                        ctr = ctr.at[ix_b["spilled"]].add(spill_b)
                        ctr = ctr.at[ix_b["occupied"]].add(
                            jnp.any(el_b).astype(jnp.int32))
                        ctr = ctr.at[ix_b["starved"]].add(
                            jnp.any(el_b & ~take_b).astype(jnp.int32))
                    # flight-recorder update — BEFORE the hops, against the
                    # step-start qvalid, so a taken frame's vector already
                    # includes this step's transit when it rides the link.
                    # The three predicates are disjoint and cover every
                    # live queued frame: taken (one hop on this axis),
                    # eligible-but-left-waiting (credit/QoS stall), or
                    # valid-but-off-axis (ingress/phase queue wait).
                    taken = jnp.zeros_like(qvalid)
                    if use_fwd:
                        taken = taken | take_f
                    if use_bwd:
                        taken = taken | take_b
                    enter = qatt[:, ATT_ENTER]
                    qatt = qatt.at[:, ATT_ENTER].set(
                        jnp.where(taken & (enter == 0), step_no, enter)
                    )
                    qatt = qatt.at[:, N_ATT_FIXED + ai].add(
                        taken.astype(jnp.int32)
                    )
                    qatt = qatt.at[:, ATT_STALL].add(
                        (elig & ~taken).astype(jnp.int32)
                    )
                    qatt = qatt.at[:, ATT_WAIT].add(
                        (qvalid & ~elig).astype(jnp.int32)
                    )
                    if defect:
                        qatt = qatt.at[:, ATT_DEFECT].add(
                            (extra_b | extra_f).astype(jnp.int32)
                        )
                    arrs, avalids, adirs, aatts = [], [], [], []
                    ex = qdir if defect else None
                    if use_fwd:
                        arr_f, av_f, ad_f, aa_f = hop(queue, take_f, axis,
                                                      fwd_perm, qatt,
                                                      extra=ex)
                        qvalid = qvalid & ~take_f
                        arrs.append(arr_f)
                        avalids.append(av_f)
                        adirs.append(ad_f)
                        aatts.append(aa_f)
                    if use_bwd:
                        arr_b, av_b, ad_b, aa_b = hop(queue, take_b, axis,
                                                      bwd_perm, qatt,
                                                      extra=ex)
                        qvalid = qvalid & ~take_b
                        arrs.append(arr_b)
                        avalids.append(av_b)
                        adirs.append(ad_b)
                        aatts.append(aa_b)
                    arr = jnp.concatenate(arrs)
                    avalid = jnp.concatenate(avalids)
                    aatt = jnp.concatenate(aatts)
                    # deliver frames that reached their full destination
                    done = avalid & (route_dst(arr) == me)
                    rx, rx_cnt, rx_step, rx_att, ok = _append(
                        rx, rx_cnt, rx_step, rx_att, ok, arr, done, step_no,
                        aatt,
                    )
                    ctr = ctr.at[IDX_DELIVERED].add(count(done))
                    # transit frames re-queue at the FRONT (FIFO per path);
                    # the hoisted columns ride the same stable partition
                    cvalid = jnp.concatenate([avalid & ~done, qvalid])
                    comb = jnp.concatenate([arr, queue])
                    catt = jnp.concatenate([aatt, qatt])
                    cdst = jnp.concatenate([
                        self._coord(route_dst(arr), ai).astype(jnp.int32),
                        qdst,
                    ])
                    clvl = jnp.concatenate([arr[:, HDR_LEVEL], qlvl])
                    cadp = jnp.concatenate([route_adaptive(arr), qadp])
                    csrc = jnp.concatenate([
                        self._coord(route_src(arr), ai).astype(jnp.int32),
                        qsrc,
                    ])
                    if defect:
                        cdir = jnp.concatenate([jnp.concatenate(adirs), qdir])
                        qvalid, (queue, qdst, qlvl, qadp, qdir, qsrc,
                                 qatt), over = \
                            _compact_to(cvalid, q_cap, comb, cdst, clvl,
                                        cadp, cdir, csrc, catt)
                        ok = ok & ~over
                        return (queue, qdst, qlvl, qadp, qdir, qvalid,
                                rx, rx_cnt, rx_step, ok, step_no, sf, sb,
                                qsrc, ctr, qatt, rx_att)
                    qvalid, (queue, qdst, qlvl, qadp, qsrc, qatt), over = \
                        _compact_to(cvalid, q_cap, comb, cdst, clvl, cadp,
                                    csrc, catt)
                    ok = ok & ~over
                    return (queue, qdst, qlvl, qadp, qvalid,
                            rx, rx_cnt, rx_step, ok, step_no,
                            qsrc, ctr, qatt, rx_att)

                if defect:
                    init = (queue, qdst, qlvl, qadp,
                            jnp.zeros((q_cap,), jnp.int32), qvalid,
                            rx, rx_cnt, rx_step, ok, step_no,
                            jnp.int32(0), jnp.int32(0), qsrc, ctr,
                            qatt, rx_att)
                else:
                    init = (queue, qdst, qlvl, qadp, qvalid,
                            rx, rx_cnt, rx_step, ok, step_no, qsrc, ctr,
                            qatt, rx_att)

                if cfg.early_exit:
                    # stop as soon as no device anywhere still holds a frame
                    # that needs this axis: the static bound becomes a cap,
                    # not the price every tick pays.  `more` must be GLOBAL
                    # (psum over the whole mesh) so every device agrees on
                    # the trip count and the ppermutes stay matched.
                    def more_of(c, n_axis=n_axis, myc=myc):
                        # c[1] = qdst, c[5 or 4] = qvalid (defect carries an
                        # extra qdir column before it)
                        live = c[5 if defect else 4] & (
                            ((c[1] - myc) % n_axis) != 0
                        )
                        return jax.lax.psum(
                            jnp.any(live).astype(jnp.int32), axes
                        ) > 0

                    def body(c, step=step, more_of=more_of):
                        it, c = c[0], step(c[1:-1])
                        return (it + 1,) + c + (more_of(c),)

                    def wcond(c, steps=steps):
                        return (c[0] < steps) & c[-1]

                    out = jax.lax.while_loop(
                        wcond, body,
                        (jnp.int32(0),) + init + (jnp.bool_(True),),
                    )[1:-1]
                else:
                    out, _ = jax.lax.scan(
                        lambda c, _, step=step: (step(c), None),
                        init, None, length=steps,
                    )
                if defect:
                    (queue, qdst, qlvl, qadp, _, qvalid,
                     rx, rx_cnt, rx_step, ok, step_no, _, _, _, ctr,
                     qatt, rx_att) = out
                else:
                    (queue, qdst, qlvl, qadp, qvalid,
                     rx, rx_cnt, rx_step, ok, step_no, _, ctr,
                     qatt, rx_att) = out

            # anything still queued is undeliverable (bad dst / starved link)
            ok = ok & ~jnp.any(qvalid)
            live = jnp.arange(rx_cap) < rx_cnt
            frame_crc = verify_frames(rx)
            crc_ok = jnp.all(jnp.where(live, frame_crc, True))
            ctr = ctr.at[IDX_CRC_FAIL].add(
                jnp.sum(live & ~frame_crc, dtype=jnp.int32)
            )
            return (rx[None], rx_cnt[None], ok[None], crc_ok[None],
                    rx_step[None], rx_att[None], ctr[None])

        return local

    # -- fused single-jit tick ---------------------------------------------

    def deliver_fused(
        self,
        payloads: np.ndarray,  # (R, Bmax, Wcap) u32 — sends grouped by src
        nbytes: np.ndarray,  # (R, Bmax) int32 true byte lengths
        routes: np.ndarray,  # (R, Bmax, 3) int32 (src, dst, seq0)
        levels: np.ndarray,  # (R, Bmax) uint32 per-send ListLevels
        send_valid: np.ndarray,  # (R, Bmax) bool — real send vs padding row
        axis_steps: Tuple[Tuple[int, int], ...],
        total: int,
        faults: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None,
    ):
        """One fused tick: frame every rank's sends, lay the live frames out
        as that rank's TX queue, run the routed scan, and split the
        delivered frames into (headers, payloads) — ONE
        ``jax.jit(shard_map(...))``, every stage per-device, no host round
        trips and no cross-device data motion beyond the routing ppermutes
        themselves.

        ``faults`` (the :class:`~repro.fabric.faults.FaultPlan` injection
        point, mapped to this engine's canonical row layout by the
        mailbox) is ``(gather (R, T) int32, xor (R, T, W) u32, valid
        (R, T) bool)``: after framing, each rank's TX queue becomes
        ``tx[gather] ^ xor`` with ``valid`` as the post-fault liveness —
        drop, corrupt, duplicate, and reorder all reduce to this one
        gather+xor, so the injected tick stays a single jit.

        Returns device arrays ``(rx_hdr (R, cap, HDR_WORDS), rx_pay
        (R, cap, frame_words), rx_cnt, ok, crc_ok, rx_step, rx_att,
        counters)`` (``rx_att`` per-frame in the ``ATT_*`` layout,
        ``counters`` per-rank in the ``repro.obs.counters`` layout); the
        caller materializes host bytes only at reassembly time
        (``Mailbox.recv``).
        """
        key = (payloads.shape[1], payloads.shape[2], axis_steps, total,
               faults is not None)
        fn = self._fused.get(key)
        if fn is None:
            fn = self._fused[key] = self._build_fused(
                payloads.shape[1], payloads.shape[2], axis_steps, total,
                faulted=faults is not None,
            )
        base = (
            jnp.asarray(payloads), jnp.asarray(nbytes), jnp.asarray(routes),
            jnp.asarray(levels), jnp.asarray(send_valid),
        )
        if faults is None:
            return fn(*base)
        gather, xor, fvalid = faults
        return fn(*base, jnp.asarray(gather), jnp.asarray(xor),
                  jnp.asarray(fvalid))

    def _build_fused(
        self, Bmax: int, Wcap: int,
        axis_steps: Tuple[Tuple[int, int], ...], total: int,
        faulted: bool = False,
    ):
        # deferred import: keep package init order independent
        from .frames import frame_parts_batch

        cfg = self.config
        W = cfg.frame_width
        phits = cfg.frame_phits
        frame_words = phits * PHIT_WORDS
        F = Wcap // frame_words + 1  # + terminator
        T = Bmax * F  # a rank's TX queue is exactly its own frames
        rx_cap, q_cap = self._capacities(T, total)
        route_local = self._build_local(T, axis_steps, q_cap, rx_cap)
        adaptive = cfg.adaptive

        def local(payloads, nbytes, routes, levels, svalid,
                  gather=None, xorv=None, fvalid=None):
            # (1, Bmax, …) — one device's pending sends.  Framing here means
            # the frames are BORN on the rank that owns them: no global
            # scatter, no resharding — the only cross-device traffic in the
            # whole tick is the routing ppermutes.
            hdr, data, _ = frame_parts_batch(
                payloads[0], nbytes[0], routes[0], list_level=levels[0],
                frame_phits=phits, adaptive=adaptive,
            )
            # wire-layout assembly (the Pallas assemble kernel's jnp twin —
            # inside shard_map the concat is free; the kernel remains the
            # unfused/TPU path)
            frames = jnp.concatenate([hdr, data], axis=-1)  # (Bmax, F, W)
            tx = frames.reshape(1, T, W)
            # frame f of send i is live iff f < frame_capacity(nbytes_i)
            words = (nbytes[0] + 3) // 4
            n_live = -(-words // frame_words) + 1
            fidx = jnp.arange(F, dtype=jnp.int32)[None, :]
            tx_valid = (
                svalid[0][:, None] & (fidx < n_live[:, None])
            ).reshape(1, T)
            if gather is not None:
                # fault injection: the post-fault queue is a gather of the
                # canonical rows (drop = row masked out, dup = row sourced
                # twice, reorder = permuted gather) XOR a corruption mask
                tx = (tx[0][gather[0]] ^ xorv[0])[None]
                tx_valid = fvalid
            rx, rx_cnt, ok, crc_ok, rx_step, rx_att, ctr = route_local(
                tx, tx_valid
            )
            # RX split, per-device (slicing — bit-identical to the Pallas
            # ``unpack_frames_batch`` twin used by the three-program path)
            return (
                rx[:, :, :HDR_WORDS], rx[:, :, HDR_WORDS:],
                rx_cnt, ok, crc_ok, rx_step, rx_att, ctr,
            )

        spec = P(self.axis_names)
        return jax.jit(
            jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(spec,) * (8 if faulted else 5),
                out_specs=(spec,) * 8,
                check_vma=False,
            )
        )
