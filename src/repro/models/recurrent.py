"""Recurrent blocks: xLSTM (mLSTM + sLSTM) and RG-LRU (RecurrentGemma).

Training forms:
* **mLSTM** — chunkwise-parallel form (the xLSTM paper's training mode):
  intra-chunk attention-like einsums + an inter-chunk recurrence over the
  per-head matrix memory C ∈ R^{dh×dh}.  Linear in S.
* **sLSTM** — inherently sequential (recurrent gate connections); scanned
  over time with input projections hoisted out of the loop.  Linear in S.
* **RG-LRU** — gated linear recurrence computed with
  ``jax.lax.associative_scan`` (log-depth, no while loop → exact
  cost_analysis) + short conv1d, per RecurrentGemma.

Decode forms: single-step state updates; state replaces the KV cache.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, rms_norm


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
def _mlstm_gates(cfg, p, x):
    """Returns (q, k, v, i_tilde, f_tilde) for x: (B, S, D)."""
    B, S, D = x.shape
    H, dh = cfg.n_heads, cfg.dh
    q = (x @ p["wq"]).reshape(B, S, H, dh) * (dh ** -0.5)
    k = (x @ p["wk"]).reshape(B, S, H, dh) * (dh ** -0.5)
    v = (x @ p["wv"]).reshape(B, S, H, dh)
    it = (x @ p["wi"]).astype(jnp.float32)                  # (B, S, H)
    ft = (x @ p["wf"]).astype(jnp.float32) + p["bf"].astype(jnp.float32)
    return q, k, v, it, ft


def mlstm_train(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                chunk: int = 256) -> jax.Array:
    """Chunkwise-parallel mLSTM. x: (B, S, D) -> (B, S, D)."""
    B, S, D = x.shape
    H, dh = cfg.n_heads, cfg.dh
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    Sp = x.shape[1]
    nc = Sp // c
    q, k, v, it, ft = _mlstm_gates(cfg, p, x)
    # reshape into chunks: (B, nc, c, H, ...)
    qc = q.reshape(B, nc, c, H, dh)
    kc = k.reshape(B, nc, c, H, dh)
    vc = v.reshape(B, nc, c, H, dh)
    itc = it.reshape(B, nc, c, H)
    ftc = ft.reshape(B, nc, c, H)
    logsig_f = jax.nn.log_sigmoid(ftc)                      # (B, nc, c, H)
    csum_f = jnp.cumsum(logsig_f, axis=2)                   # within chunk
    total_f = csum_f[:, :, -1]                              # (B, nc, H)

    def body(carry, xs):
        C_prev, n_prev, m_prev = carry
        qi, ki, vi, iti, fi_csum, fi_tot = xs
        # log decay from chunk start to position t: fi_csum (B, c, H)
        # intra-chunk D matrix: D[t,s] = exp(csum_t - csum_s + i_s) (s<=t)
        lg_q = fi_csum                                       # (B, c, H)
        a = lg_q[:, :, None, :] - fi_csum[:, None, :, :] + iti[:, None, :, :]
        mask = jnp.tril(jnp.ones((c, c), bool))
        a = jnp.where(mask[None, :, :, None], a, -jnp.inf)
        # inter-chunk: contribution decays by csum_t from chunk start
        b = lg_q + m_prev[:, None, :]                        # (B, c, H)
        m_new = jnp.maximum(jnp.max(a, axis=2), b)           # (B, c, H)
        Dmat = jnp.exp(a - m_new[:, :, None, :])             # (B, c, c, H)
        scale_q = jnp.exp(b - m_new)                         # (B, c, H)
        # intra: (q_t · k_s) D[t,s] v_s
        s_qk = jnp.einsum("bthd,bshd->btsh", qi, ki,
                          preferred_element_type=jnp.float32)
        intra = jnp.einsum("btsh,btsh,bshd->bthd", s_qk, Dmat,
                           vi.astype(jnp.float32))
        # inter: q_t · C_prev, decayed
        inter = jnp.einsum("bthd,bhde->bthe", qi.astype(jnp.float32), C_prev)
        inter = inter * scale_q[..., None]
        num = intra + inter
        # normalizer n
        n_intra = jnp.einsum("btsh,btsh,bshd->bthd", s_qk, Dmat,
                             jnp.ones_like(vi, jnp.float32))[..., :1]
        n_inter = (jnp.einsum("bthd,bhd->bth", qi.astype(jnp.float32), n_prev)
                   * scale_q)[..., None]
        denom = jnp.maximum(jnp.abs(n_intra + n_inter), 1.0)
        h = (num / denom).astype(x.dtype)                    # (B, c, H, dh)
        # update inter-chunk state: C = exp(f_tot + m_prev - m_next)·C_prev
        #                               + Σ_s exp(f_tot - csum_s + i_s)·k_s v_sᵀ
        m_next = jnp.maximum(fi_tot + m_prev, jnp.max(
            fi_tot[:, None] - fi_csum + iti, axis=1))        # (B, H)
        dec = jnp.exp(fi_tot + m_prev - m_next)              # (B, H)
        w_s = jnp.exp(fi_tot[:, None] - fi_csum + iti - m_next[:, None])
        C_new = (C_prev * dec[..., None, None] +
                 jnp.einsum("bsh,bshd,bshe->bhde", w_s,
                            ki.astype(jnp.float32), vi.astype(jnp.float32)))
        n_new = (n_prev * dec[..., None] +
                 jnp.einsum("bsh,bshd->bhd", w_s, ki.astype(jnp.float32)))
        return (C_new, n_new, m_next), h

    C0 = jnp.zeros((B, H, dh, dh), jnp.float32)
    n0 = jnp.zeros((B, H, dh), jnp.float32)
    m0 = jnp.full((B, H), -1e30, jnp.float32)
    xs = (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
          jnp.moveaxis(itc, 1, 0), jnp.moveaxis(csum_f, 1, 0),
          jnp.moveaxis(total_f, 1, 0))
    (Cf, nf, mf), hs = jax.lax.scan(body, (C0, n0, m0), xs, length=nc)
    h = jnp.moveaxis(hs, 0, 1).reshape(B, Sp, H * dh)[:, :S]
    return h, {"C": Cf, "n": nf, "m": mf}


def mlstm_block(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                return_state: bool = False):
    """Full mLSTM residual block: norm → mLSTM → out-proj → gated MLP."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    inner, state = mlstm_train(cfg, p, h, chunk=cfg.mlstm_chunk)
    y = inner @ p["wo"]
    u, g = jnp.split(h @ p["up"], 2, axis=-1)
    y = y + (jax.nn.silu(g) * u) @ p["down"]
    out = x + y
    return (out, state) if return_state else out


def mlstm_init_state(cfg: ModelConfig, batch: int):
    H, dh = cfg.n_heads, cfg.dh
    return {"C": jnp.zeros((batch, H, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, H, dh), jnp.float32),
            "m": jnp.full((batch, H), -1e30, jnp.float32)}


def mlstm_step(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
               state: Dict[str, jax.Array]):
    """Single decode step. x: (B, 1, D)."""
    B = x.shape[0]
    H, dh = cfg.n_heads, cfg.dh
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v, it, ft = _mlstm_gates(cfg, p, h)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]         # (B, H, dh)
    it, ft = it[:, 0], ft[:, 0]                  # (B, H)
    lf = jax.nn.log_sigmoid(ft)
    m_new = jnp.maximum(lf + state["m"], it)
    fd = jnp.exp(lf + state["m"] - m_new)[..., None]
    iw = jnp.exp(it - m_new)[..., None]
    C = state["C"] * fd[..., None] + (iw[..., None] *
                                      k.astype(jnp.float32)[..., :, None] *
                                      v.astype(jnp.float32)[..., None, :])
    n = state["n"] * fd + iw * k.astype(jnp.float32)
    num = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q.astype(jnp.float32),
                                         n))[..., None], 1.0)
    y = ((num / den).astype(x.dtype)).reshape(B, 1, H * dh) @ p["wo"]
    u, g = jnp.split(h @ p["up"], 2, axis=-1)
    y = y + (jax.nn.silu(g) * u) @ p["down"]
    return x + y, {"C": C, "n": n, "m": m_new}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------
def slstm_block(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                return_state: bool = False):
    """sLSTM residual block, scanned over time (sequential recurrence)."""
    B, S, D = x.shape
    H = cfg.n_heads
    dh = D // H
    hin = rms_norm(x, p["ln1"], cfg.norm_eps)
    # input projections hoisted out of the time loop
    zx = (hin @ p["wz"]).reshape(B, S, H, dh)
    ix = (hin @ p["wi"]).astype(jnp.float32).reshape(B, S, H, dh)
    fx = (hin @ p["wf"]).astype(jnp.float32).reshape(B, S, H, dh)
    ox = (hin @ p["wo_gate"]).reshape(B, S, H, dh)

    def step(carry, xs):
        c_prev, h_prev, m_prev = carry
        zt, itl, ftl, otl = xs
        # recurrent contribution (block-diagonal per head)
        zr = jnp.einsum("bhd,hde->bhe", h_prev, p["rz"])
        z = jnp.tanh(zt + zr)
        i_t = itl
        f_t = ftl
        m_t = jnp.maximum(f_t + m_prev, i_t)
        ig = jnp.exp(i_t - m_t)
        fg = jnp.exp(f_t + m_prev - m_t)
        c_t = fg * c_prev + ig * z.astype(jnp.float32)
        o_t = jax.nn.sigmoid(otl.astype(jnp.float32))
        h_t = (o_t * jnp.tanh(c_t)).astype(x.dtype)
        return (c_t, h_t, m_t), h_t

    c0 = jnp.zeros((B, H, dh), jnp.float32)
    h0 = jnp.zeros((B, H, dh), x.dtype)
    m0 = jnp.zeros((B, H, dh), jnp.float32)
    xs = (jnp.moveaxis(zx, 1, 0), jnp.moveaxis(ix, 1, 0),
          jnp.moveaxis(fx, 1, 0), jnp.moveaxis(ox, 1, 0))
    (cf, hf, mf), hs = jax.lax.scan(step, (c0, h0, m0), xs, length=S)
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, D)
    y = h @ p["wo"]
    u, g = jnp.split(hin @ p["up"], 2, axis=-1)
    y = y + (jax.nn.silu(g) * u) @ p["down"]
    out = x + y
    return (out, {"c": cf, "h": hf, "m": mf}) if return_state else out


def slstm_init_state(cfg: ModelConfig, batch: int):
    H = cfg.n_heads
    dh = cfg.d_model // H
    return {"c": jnp.zeros((batch, H, dh), jnp.float32),
            "h": jnp.zeros((batch, H, dh), jnp.dtype(cfg.dtype)),
            "m": jnp.zeros((batch, H, dh), jnp.float32)}


def slstm_step(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
               state: Dict[str, jax.Array]):
    B = x.shape[0]
    H = cfg.n_heads
    dh = cfg.d_model // H
    hin = rms_norm(x, p["ln1"], cfg.norm_eps)
    h1 = hin[:, 0]
    zt = (h1 @ p["wz"]).reshape(B, H, dh)
    it = (h1 @ p["wi"]).astype(jnp.float32).reshape(B, H, dh)
    ft = (h1 @ p["wf"]).astype(jnp.float32).reshape(B, H, dh)
    ot = (h1 @ p["wo_gate"]).reshape(B, H, dh)
    zr = jnp.einsum("bhd,hde->bhe", state["h"], p["rz"])
    z = jnp.tanh(zt + zr)
    m_t = jnp.maximum(ft + state["m"], it)
    ig = jnp.exp(it - m_t)
    fg = jnp.exp(ft + state["m"] - m_t)
    c_t = fg * state["c"] + ig * z.astype(jnp.float32)
    h_t = (jax.nn.sigmoid(ot.astype(jnp.float32)) * jnp.tanh(c_t)).astype(x.dtype)
    y = h_t.reshape(B, 1, cfg.d_model) @ p["wo"]
    u, g = jnp.split(hin @ p["up"], 2, axis=-1)
    y = y + (jax.nn.silu(g) * u) @ p["down"]
    return x + y, {"c": c_t, "h": h_t, "m": m_t}


# ---------------------------------------------------------------------------
# RG-LRU (RecurrentGemma)
# ---------------------------------------------------------------------------
_RGLRU_C = 8.0


def rglru_block(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                return_state: bool = False):
    """RG-LRU residual block: in-proj → conv1d(4) → gated linear recurrence
    (associative scan) → out-proj."""
    B, S, D = x.shape
    F = p["conv"].shape[1]          # lru width (= d_model in RecurrentGemma)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    u, gate = jnp.split(h @ p["w_in"], 2, axis=-1)            # (B, S, F) ×2
    # short causal conv1d (kernel 4) over time
    uc = _causal_conv4(u, p["conv"])
    # gates
    r = jax.nn.sigmoid((uc @ p["wa"]).astype(jnp.float32))     # recurrence gate
    i = jax.nn.sigmoid((uc @ p["wx"]).astype(jnp.float32))     # input gate
    log_a = -_RGLRU_C * jax.nn.softplus(p["lam"]) * r          # (B, S, F)
    a = jnp.exp(log_a)
    gated_x = uc.astype(jnp.float32) * i
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6))
    xin = gated_x * beta

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    _, y = jax.lax.associative_scan(combine, (a, xin), axis=1)
    out_gated = (y * jax.nn.gelu(gate.astype(jnp.float32))).astype(x.dtype)
    out = x + out_gated @ p["w_out"]
    if not return_state:
        return out
    # decode state: last recurrence value + last 3 raw conv inputs
    hist = u[:, -3:, :] if S >= 3 else jnp.pad(u, ((0, 0), (3 - S, 0), (0, 0)))
    return out, {"y": y[:, -1], "conv": hist}


def _causal_conv4(u: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv, kernel 4. u: (B, S, F); w: (4, F)."""
    out = u * w[3]
    for i in range(1, 4):
        shifted = jnp.pad(u, ((0, 0), (i, 0), (0, 0)))[:, :-i]
        out = out + shifted * w[3 - i]
    return out


def rglru_init_state(cfg: ModelConfig, batch: int):
    W = cfg.d_model
    return {"y": jnp.zeros((batch, W), jnp.float32),
            "conv": jnp.zeros((batch, 3, W), jnp.dtype(cfg.dtype))}


def rglru_step(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
               state: Dict[str, jax.Array]):
    B = x.shape[0]
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    u, gate = jnp.split(h[:, 0] @ p["w_in"], 2, axis=-1)       # (B, F)
    hist = state["conv"]                                        # (B, 3, F)
    uc = (u * p["conv"][3] + hist[:, 2] * p["conv"][2] +
          hist[:, 1] * p["conv"][1] + hist[:, 0] * p["conv"][0])
    new_hist = jnp.concatenate([hist[:, 1:], u[:, None]], axis=1)
    r = jax.nn.sigmoid((uc @ p["wa"]).astype(jnp.float32))
    i = jax.nn.sigmoid((uc @ p["wx"]).astype(jnp.float32))
    log_a = -_RGLRU_C * jax.nn.softplus(p["lam"]) * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6))
    y = state["y"] * a + uc.astype(jnp.float32) * i * beta
    out = (y * jax.nn.gelu(gate.astype(jnp.float32))).astype(x.dtype)
    return x + (out @ p["w_out"])[:, None], {"y": y, "conv": new_hist}
