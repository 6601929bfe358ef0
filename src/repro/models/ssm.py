"""Sequence-mixing SSM blocks: Mamba-2 (SSD), mLSTM and sLSTM.

All three support a chunkwise-parallel full-sequence form (train / prefill)
and an O(1)-state single-step form (decode) — this is what makes the
``long_500k`` cells runnable for jamba / xlstm (DESIGN.md §5).

Chunked SSD formulation (within-chunk quadratic, inter-chunk recurrent):
for chunk-local log-decay cumsum ``cum``, the intra-chunk term is a masked
(L, L) matmul and the carried state advances by ``exp(cum_L)`` — the same
skeleton serves Mamba (state (H, P, N)) and mLSTM (state (H, Dh, Dh)).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import dense_init, rmsnorm
from ..configs.base import ModelConfig

CHUNK = 256


def _pad_to_chunks(x, axis=1, chunk=CHUNK):
    S = x.shape[axis]
    pad = (-S) % chunk
    if pad:
        padw = [(0, 0)] * x.ndim
        padw[axis] = (0, pad)
        x = jnp.pad(x, padw)
    return x, S


# ===========================================================================
# Mamba-2 (SSD)
# ===========================================================================


def mamba_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    d_in = cfg.ssm_expand * cfg.d_model
    nh = d_in // cfg.ssm_head
    return d_in, nh, cfg.ssm_head, cfg.ssm_state


def mamba_conv_dim(cfg: ModelConfig) -> int:
    """Channels of the depthwise conv: x, B and C (one group) together."""
    d_in, _, _, N = mamba_dims(cfg)
    return d_in + 2 * N


def init_mamba(key, cfg: ModelConfig, dtype) -> Dict:
    d = cfg.d_model
    d_in, nh, P, N = mamba_dims(cfg)
    conv_dim = mamba_conv_dim(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(k1, (d, 2 * d_in + 2 * N + nh), dtype=dtype),
        "conv_w": (jax.random.normal(k2, (cfg.ssm_conv, conv_dim)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": jnp.zeros((d_in,), dtype),
        "out_proj": dense_init(k3, (d_in, d), dtype=dtype,
                               scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }


def _mamba_in(p, x, cfg, conv_state):
    """Mamba-2's input side: in_proj to (z, xBC, dt), the causal conv and
    SiLU over x, B and C together, then the split.  Returns (z, xs, B, C, dt,
    new conv state)."""
    d_in, nh, P, N = mamba_dims(cfg)
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * N], -1)
    xbc, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xs, Bm, Cm = jnp.split(xbc, [d_in, d_in + N], -1)
    return z, xs, Bm, Cm, dt, conv_state


def _causal_conv(xs, w, b, state=None):
    """Depthwise causal conv over time.  xs (B,S,D); w (K,D).  Returns
    (out, new_state) with state = last K-1 inputs."""
    K = w.shape[0]
    B, S, D = xs.shape
    if state is None:
        state = jnp.zeros((B, K - 1, D), xs.dtype)
    xcat = jnp.concatenate([state, xs], axis=1)  # (B, S+K-1, D)
    out = sum(xcat[:, i : i + S] * w[i][None, None, :] for i in range(K))
    new_state = xcat[:, S:, :] if K > 1 else state
    return jax.nn.silu(out + b), new_state


def _gated_norm(p, y, z, cfg):
    """Mamba-2's output norm: RMSNorm of y * silu(z) in float32 (one group),
    at the configuration's eps."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return rmsnorm(g, p["norm"], cfg.norm_eps).astype(z.dtype)


def mamba_forward(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, state: Optional[Dict] = None
) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence Mamba (chunked SSD).  x (B,S,d) -> (out, new_state)."""
    B, S, d = x.shape
    d_in, nh, P, N = mamba_dims(cfg)
    z, xs, Bm, Cm, dt, conv_state = _mamba_in(p, x, cfg, state["conv"] if state else None)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,nh)
    A = -jnp.exp(p["A_log"])  # (nh,)
    loga = dt * A[None, None, :]  # (B,S,nh) log-decay per step
    xh = xs.astype(jnp.float32).reshape(B, S, nh, P)
    Bm = Bm.astype(jnp.float32)  # (B,S,N) shared across heads
    Cm = Cm.astype(jnp.float32)

    # pad to chunks
    L = min(CHUNK, max(16, S))
    xh, _ = _pad_to_chunks(xh, 1, L)
    Bp, _ = _pad_to_chunks(Bm, 1, L)
    Cp, _ = _pad_to_chunks(Cm, 1, L)
    la, _ = _pad_to_chunks(loga, 1, L)
    dtp, _ = _pad_to_chunks(dt, 1, L)
    nC = xh.shape[1] // L
    xh = xh.reshape(B, nC, L, nh, P)
    Bp = Bp.reshape(B, nC, L, N)
    Cp = Cp.reshape(B, nC, L, N)
    la = la.reshape(B, nC, L, nh)
    dtp = dtp.reshape(B, nC, L, nh)

    ssm0 = state["ssm"] if state else jnp.zeros((B, nh, P, N), jnp.float32)

    def chunk_step(S_prev, inp):
        xc, Bc, Cc, lac, dtc = inp  # (B,L,...) for one chunk
        cum = jnp.cumsum(lac, axis=1)  # (B,L,nh)
        # intra-chunk: y[t] += sum_{s<=t} exp(cum_t - cum_s) dt_s (Cc_t.Bc_s) x_s
        seg = cum[:, :, None, :] - cum[:, None, :, :]  # (B,L,L,nh)
        mask = jnp.tril(jnp.ones((L, L), bool))
        # mask BEFORE exp: s > t gives seg >= 0 which overflows (and then
        # poisons the cotangent through jnp.where).
        decay = jnp.exp(jnp.where(mask[None, :, :, None], seg, -1e30))
        cb = jnp.einsum("btn,bsn->bts", Cc, Bc)  # (B,L,L)
        w = cb[:, :, :, None] * decay * dtc[:, None, :, :]  # (B,t,s,nh)
        y = jnp.einsum("btsh,bshp->bthp", w, xc)
        # inter-chunk: y[t] += Cc_t . (exp(cum_t) * S_prev)
        y = y + jnp.einsum("btn,bth,bhpn->bthp", Cc, jnp.exp(cum), S_prev)
        # state advance: S_new = exp(cum_L) S_prev + sum_s exp(cum_L - cum_s) dt_s B_s x_s
        tail = jnp.exp(cum[:, -1:, :] - cum)  # (B,L,nh)
        S_new = (
            jnp.exp(cum[:, -1, :])[:, :, None, None] * S_prev
            + jnp.einsum("bsh,bshp,bsn->bhpn", tail * dtc, xc, Bc)
        )
        return S_new, y

    S_fin, ys = jax.lax.scan(
        chunk_step,
        ssm0,
        (
            xh.swapaxes(0, 1), Bp.swapaxes(0, 1), Cp.swapaxes(0, 1),
            la.swapaxes(0, 1), dtp.swapaxes(0, 1),
        ),
    )
    y = ys.swapaxes(0, 1).reshape(B, nC * L, nh, P)[:, :S]
    y = y + xh.reshape(B, nC * L, nh, P)[:, :S] * p["D"][None, None, :, None]
    y = _gated_norm(p, y.reshape(B, S, d_in), z, cfg)
    out = y @ p["out_proj"]
    return out, {"conv": conv_state, "ssm": S_fin}


def mamba_decode(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, state: Dict
) -> Tuple[jnp.ndarray, Dict]:
    """Single-step Mamba.  x (B,1,d)."""
    B = x.shape[0]
    d_in, nh, P, N = mamba_dims(cfg)
    z, xs, Bm, Cm, dt, conv_state = _mamba_in(p, x, cfg, state["conv"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])[:, 0]  # (B,nh)
    A = -jnp.exp(p["A_log"])
    da = jnp.exp(dt * A[None, :])  # (B,nh)
    xh = xs.astype(jnp.float32).reshape(B, nh, P)
    Bv = Bm.astype(jnp.float32)[:, 0]  # (B,N)
    Cv = Cm.astype(jnp.float32)[:, 0]
    S_new = da[:, :, None, None] * state["ssm"] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xh, Bv
    )
    y = jnp.einsum("bn,bhpn->bhp", Cv, S_new) + xh * p["D"][None, :, None]
    y = _gated_norm(p, y.reshape(B, 1, d_in), z, cfg)
    return y @ p["out_proj"], {"conv": conv_state, "ssm": S_new}


def mamba_init_state(cfg: ModelConfig, batch: int, dtype) -> Dict:
    d_in, nh, P, N = mamba_dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, mamba_conv_dim(cfg)), dtype),
        "ssm": jnp.zeros((batch, nh, P, N), jnp.float32),
    }


# ===========================================================================
# mLSTM (xLSTM): matrix memory, exponential gating, chunkwise parallel
# ===========================================================================


def mlstm_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    d_in = cfg.ssm_expand * cfg.d_model
    nh = cfg.n_heads
    return d_in, nh, d_in // nh


def init_mlstm(key, cfg: ModelConfig, dtype) -> Dict:
    d = cfg.d_model
    d_in, nh, dh = mlstm_dims(cfg)
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (d, d_in), dtype=dtype),
        "wk": dense_init(ks[1], (d, d_in), dtype=dtype),
        "wv": dense_init(ks[2], (d, d_in), dtype=dtype),
        "wif": dense_init(ks[3], (d, 2 * nh), dtype=jnp.float32),
        "b_if": jnp.concatenate([jnp.zeros((nh,)), 3.0 * jnp.ones((nh,))]),
        "wo_gate": dense_init(ks[4], (d, d_in), dtype=dtype),
        "out_proj": dense_init(ks[5], (d_in, d), dtype=dtype,
                               scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }


def _mlstm_qkvif(p, x, cfg):
    d_in, nh, dh = mlstm_dims(cfg)
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, nh, dh) / (dh**0.5)
    k = (x @ p["wk"]).reshape(B, S, nh, dh)
    v = (x @ p["wv"]).reshape(B, S, nh, dh)
    i_f = x.astype(jnp.float32) @ p["wif"] + p["b_if"]
    i_pre, f_pre = jnp.split(i_f, 2, -1)  # (B,S,nh)
    logf = jax.nn.log_sigmoid(f_pre)
    o = jax.nn.sigmoid(x @ p["wo_gate"])
    return q, k, v, i_pre, logf, o


def mlstm_forward(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, state: Optional[Dict] = None
) -> Tuple[jnp.ndarray, Dict]:
    """Chunkwise-parallel mLSTM with stabilized exponential gating."""
    B, S, d = x.shape
    d_in, nh, dh = mlstm_dims(cfg)
    q, k, v, i_pre, logf, o = _mlstm_qkvif(p, x, cfg)

    L = min(CHUNK, max(16, S))
    qp, _ = _pad_to_chunks(q.astype(jnp.float32), 1, L)
    kp, _ = _pad_to_chunks(k.astype(jnp.float32), 1, L)
    vp, _ = _pad_to_chunks(v.astype(jnp.float32), 1, L)
    ip, _ = _pad_to_chunks(i_pre, 1, L)
    # padding must not contribute: i = -inf on pad
    if qp.shape[1] != S:
        padmask = jnp.arange(qp.shape[1]) >= S
        ip = jnp.where(padmask[None, :, None], -1e30, ip)
    fp, _ = _pad_to_chunks(logf, 1, L)
    nC = qp.shape[1] // L
    rs = lambda t: t.reshape(B, nC, L, *t.shape[2:]).swapaxes(0, 1)
    qp, kp, vp, ip, fp = map(rs, (qp, kp, vp, ip, fp))

    if state is None:
        C0 = jnp.zeros((B, nh, dh, dh), jnp.float32)
        n0 = jnp.zeros((B, nh, dh), jnp.float32)
        m0 = jnp.full((B, nh), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state["C"], state["n"], state["m"]

    def chunk_step(carry, inp):
        C_prev, n_prev, m_prev = carry
        qc, kc, vc, ic, fc = inp  # (B,L,...)
        cumf = jnp.cumsum(fc, axis=1)  # (B,L,nh)
        # log-weights: intra  w_ts = cumf_t - cumf_s + i_s   (s <= t)
        #              inter  g_t  = cumf_t + m_prev
        intra = cumf[:, :, None, :] - cumf[:, None, :, :] + ic[:, None, :, :]
        mask = jnp.tril(jnp.ones((L, L), bool))
        intra = jnp.where(mask[None, :, :, None], intra, -1e30)
        inter = cumf + m_prev[:, None, :]  # (B,L,nh)
        m_t = jnp.maximum(jnp.max(intra, axis=2), inter)  # (B,L,nh)
        wi = jnp.exp(intra - m_t[:, :, None, :])  # (B,t,s,nh)
        wg = jnp.exp(inter - m_t)  # (B,L,nh)
        qk = jnp.einsum("bthd,bshd->btsh", qc, kc)
        num = (
            jnp.einsum("btsh,bshd->bthd", qk * wi, vc)
            + wg[..., None] * jnp.einsum("bthd,bhde->bthe", qc, C_prev)
        )
        den = (
            jnp.einsum("btsh,bsh->bth", qk * wi, jnp.ones_like(ic))
            + wg * jnp.einsum("bthd,bhd->bth", qc, n_prev)
        )
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # carry update
        m_new = jnp.maximum(
            cumf[:, -1, :] + m_prev, jnp.max(cumf[:, -1:, :] - cumf + ic, axis=1)
        )
        tailw = jnp.exp(cumf[:, -1:, :] - cumf + ic - m_new[:, None, :])  # (B,L,nh)
        decay = jnp.exp(cumf[:, -1, :] + m_prev - m_new)  # (B,nh)
        C_new = decay[:, :, None, None] * C_prev + jnp.einsum(
            "bsh,bshd,bshe->bhde", tailw, kc, vc
        )
        n_new = decay[:, :, None] * n_prev + jnp.einsum("bsh,bshd->bhd", tailw, kc)
        return (C_new, n_new, m_new), h

    (Cf, nf, mf), hs = jax.lax.scan(chunk_step, (C0, n0, m0), (qp, kp, vp, ip, fp))
    h = hs.swapaxes(0, 1).reshape(B, nC * L, nh, dh)[:, :S]
    h = (h.reshape(B, S, d_in) * o.astype(jnp.float32)).astype(x.dtype)
    return h @ p["out_proj"], {"C": Cf, "n": nf, "m": mf}


def mlstm_decode(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, state: Dict
) -> Tuple[jnp.ndarray, Dict]:
    B = x.shape[0]
    d_in, nh, dh = mlstm_dims(cfg)
    q, k, v, i_pre, logf, o = _mlstm_qkvif(p, x, cfg)
    q, k, v = q[:, 0].astype(jnp.float32), k[:, 0].astype(jnp.float32), v[:, 0].astype(jnp.float32)
    i_pre, logf = i_pre[:, 0], logf[:, 0]  # (B,nh)
    C, n, m = state["C"], state["n"], state["m"]
    m_new = jnp.maximum(logf + m, i_pre)
    fw = jnp.exp(logf + m - m_new)
    iw = jnp.exp(i_pre - m_new)
    C_new = fw[:, :, None, None] * C + iw[:, :, None, None] * jnp.einsum("bhd,bhe->bhde", k, v)
    n_new = fw[:, :, None] * n + iw[:, :, None] * k
    num = jnp.einsum("bhd,bhde->bhe", q, C_new)
    den = jnp.abs(jnp.einsum("bhd,bhd->bh", q, n_new))
    h = num / jnp.maximum(den, jnp.exp(-m_new))[..., None]
    h = (h.reshape(B, 1, d_in) * o.astype(jnp.float32)).astype(x.dtype)
    return h @ p["out_proj"], {"C": C_new, "n": n_new, "m": m_new}


def mlstm_init_state(cfg: ModelConfig, batch: int) -> Dict:
    d_in, nh, dh = mlstm_dims(cfg)
    return {
        "C": jnp.zeros((batch, nh, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, nh, dh), jnp.float32),
        "m": jnp.full((batch, nh), -1e30, jnp.float32),
    }


# ===========================================================================
# sLSTM (xLSTM): scalar memory + exponential gating; sequential scan
# ===========================================================================


def init_slstm(key, cfg: ModelConfig, dtype) -> Dict:
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    p = {}
    for j, g in enumerate(("i", "f", "z", "o")):
        p[f"w{g}"] = dense_init(ks[j], (d, d), dtype=jnp.float32)
        p[f"r{g}"] = dense_init(ks[4 + j], (d, d), dtype=jnp.float32, scale=0.5)
        p[f"b{g}"] = jnp.zeros((d,)) if g != "f" else 3.0 * jnp.ones((d,))
    return p


def slstm_forward(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig, state: Optional[Dict] = None
) -> Tuple[jnp.ndarray, Dict]:
    B, S, d = x.shape
    xf = x.astype(jnp.float32)
    # precompute input contributions for all steps (the only matmuls over S)
    pre = {g: xf @ p[f"w{g}"] + p[f"b{g}"] for g in ("i", "f", "z", "o")}
    if state is None:
        state = slstm_init_state(cfg, B, d)
    h0 = (state["h"], state["c"], state["n"], state["m"])

    def step(carry, t_in):
        h, c, n, m = carry
        xi, xfg, xz, xo = t_in
        i_pre = xi + h @ p["ri"]
        f_pre = xfg + h @ p["rf"]
        z = jnp.tanh(xz + h @ p["rz"])
        o = jax.nn.sigmoid(xo + h @ p["ro"])
        logf = jax.nn.log_sigmoid(f_pre)
        m_new = jnp.maximum(logf + m, i_pre)
        iw = jnp.exp(i_pre - m_new)
        fw = jnp.exp(logf + m - m_new)
        c_new = fw * c + iw * z
        n_new = fw * n + iw
        h_new = o * c_new / jnp.maximum(n_new, 1e-6)
        return (h_new, c_new, n_new, m_new), h_new

    seq = tuple(pre[g].swapaxes(0, 1) for g in ("i", "f", "z", "o"))
    (h, c, n, m), hs = jax.lax.scan(step, h0, seq)
    out = hs.swapaxes(0, 1).astype(x.dtype)
    return out, {"h": h, "c": c, "n": n, "m": m}


def slstm_decode(p: Dict, x: jnp.ndarray, cfg: ModelConfig, state: Dict):
    out, new_state = slstm_forward(p, x, cfg, state)
    return out, new_state


def slstm_init_state(cfg: ModelConfig, batch: int, d: Optional[int] = None) -> Dict:
    d = d or cfg.d_model
    z = lambda: jnp.zeros((batch, d), jnp.float32)
    return {"h": z(), "c": z(), "n": z(), "m": jnp.full((batch, d), -1e30, jnp.float32)}
