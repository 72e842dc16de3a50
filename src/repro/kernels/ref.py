"""Pure-jnp reference oracles for every Pallas kernel.

These are ALSO the XLA execution path of the model zoo
(``RunConfig.use_pallas=False``), on the CPU, on the TPU and in the
512-device dry-run. Tests compare the kernels against these functions,
interpreted on the CPU and compiled on the TPU (``chip_smoke.py``).

The attention reference is itself written flash-style (chunked online
softmax over KV blocks) so that (a) it is the mathematical oracle for the
Pallas kernel, and (b) the dry-run HLO never materializes a 32k x 32k
score matrix — HLO bytes reflect a production attention implementation.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm_ref(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with a custom VJP: math in f32, but inputs/outputs AND
    cotangents stay in the input dtype.  Without this, autodiff threads
    f32 cotangents through every residual/projection boundary — measured
    as ~2x the activation traffic and f32 (instead of bf16) tensor-
    parallel all-reduces in the backward pass (EXPERIMENTS.md §Perf).
    REPRO_RMSNORM_VJP=0 disables the custom VJP (debug escape hatch)."""
    import os
    if os.environ.get("REPRO_RMSNORM_VJP", "1") == "0":
        return _rmsnorm_fwd_math(x, w, eps)[0]
    return _rmsnorm_vjp(x, w, eps)


def _rmsnorm_fwd_math(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = lax.rsqrt(var + eps)
    y = xf * inv * w.astype(jnp.float32)
    return y.astype(x.dtype), inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_vjp(x, w, eps):
    return _rmsnorm_fwd_math(x, w, eps)[0]


def _rmsnorm_vjp_fwd(x, w, eps):
    y, inv = _rmsnorm_fwd_math(x, w, eps)
    return y, (x, w, inv)


def _rmsnorm_vjp_bwd(eps, res, g):
    x, w, inv = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    xhat = xf * inv
    gw = gf * wf
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - w.ndim)))
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rmsnorm_vjp.defvjp(_rmsnorm_vjp_fwd, _rmsnorm_vjp_bwd)


# ---------------------------------------------------------------------------
# Flash attention (chunked online softmax), GQA, causal / sliding window,
# optional q position offset (decode) and non-causal (cross attention).
# ---------------------------------------------------------------------------


def _pad_to(x: jax.Array, axis: int, mult: int) -> Tuple[jax.Array, int]:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def flash_attention_ref(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    q_offset=0,  # scalar: absolute position of q[0] (decode)
    kv_len=None,  # scalar: #valid kv positions (cache may be longer)
    sliding_window: int = 0,
    block_k: int = 512,
    scale: Optional[float] = None,
    carry_constrain=None,  # optional sharding pin for the scan carry
    custom_vjp: bool = True,
) -> jax.Array:
    """Differentiable flash attention with an O(S) *custom* backward —
    autodiff through the online-softmax scan would stack per-block score
    residuals and reintroduce the O(S^2) memory this exists to avoid.
    ``custom_vjp=False`` keeps the naive-autodiff path (§Perf baseline)."""
    Sk = k.shape[1]
    qo = jnp.asarray(q_offset, jnp.int32)
    kl = jnp.asarray(Sk if kv_len is None else kv_len, jnp.int32)
    if not custom_vjp:
        out, _ = _flash_fwd_inner(
            q, k, v, qo, kl, causal=causal, sliding_window=sliding_window,
            block_k=block_k, scale=scale, carry_constrain=carry_constrain)
        return out
    fn = _flash_vjp_factory(bool(causal), int(sliding_window), int(block_k),
                            float(scale) if scale is not None else None,
                            carry_constrain)
    return fn(q, k, v, qo, kl)


def _flash_fwd_inner(
    q, k, v, q_offset, kv_len, *,
    causal, sliding_window, block_k, scale, carry_constrain,
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % max(Hkv, 1) == 0, (Hq, Hkv)
    G = Hq // Hkv
    pin = carry_constrain if carry_constrain is not None else (lambda t: t)
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, max(Sk, 1))

    k, _ = _pad_to(k, 1, block_k)
    v, _ = _pad_to(v, 1, block_k)
    Skp = k.shape[1]
    n_blocks = Skp // block_k

    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, D)
    kf = k.astype(jnp.float32).reshape(B, Skp, Hkv, D)
    vf = v.astype(jnp.float32).reshape(B, Skp, Hkv, D)

    q_pos = q_offset + jnp.arange(Sq)  # (Sq,)
    valid_len = Sk if kv_len is None else kv_len

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, j = blk  # kb/vb: (B, block_k, Hkv, D)
        k_pos = j * block_k + jnp.arange(block_k)
        # scores: (B, Sq, Hkv, G, block_k)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kb)
        mask = k_pos[None, :] < valid_len  # (1, block_k) padded/cache tail
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if sliding_window:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - sliding_window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = pin(jnp.maximum(m, s.max(axis=-1)).reshape(B, Sq, Hkv * G)
                    ).reshape(B, Sq, Hkv, G)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = pin((l * alpha + p.sum(axis=-1)).reshape(B, Sq, Hkv * G)
                    ).reshape(B, Sq, Hkv, G)
        acc_new = (acc * alpha[..., None]
                   + jnp.einsum("bqhgk,bkhd->bqhgd", p, vb))
        acc_new = pin(acc_new.reshape(B, Sq, Hkv * G, D)
                      ).reshape(B, Sq, Hkv, G, D)
        return (m_new, l_new, acc_new), None

    m0 = pin(jnp.full((B, Sq, Hkv * G), NEG_INF, jnp.float32)
             ).reshape(B, Sq, Hkv, G)
    l0 = pin(jnp.zeros((B, Sq, Hkv * G), jnp.float32)).reshape(B, Sq, Hkv, G)
    acc0 = pin(jnp.zeros((B, Sq, Hkv * G, D), jnp.float32)
               ).reshape(B, Sq, Hkv, G, D)

    kb = kf.reshape(B, n_blocks, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = vf.reshape(B, n_blocks, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, acc0), (kb, vb, jnp.arange(n_blocks))
    )
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).reshape(B, Sq, Hq, D)
    lse = m + jnp.log(l)  # (B, Sq, Hkv, G)
    return out.astype(q.dtype), lse


def _flash_bwd_inner(
    q, k, v, q_offset, kv_len, out, lse, dout, *,
    causal, sliding_window, block_k, scale, carry_constrain,
):
    """Flash backward: per-block recompute of p; O(Sq + Sk) residuals."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    pin = carry_constrain if carry_constrain is not None else (lambda t: t)
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, max(Sk, 1))

    kp, _ = _pad_to(k, 1, block_k)
    vp, _ = _pad_to(v, 1, block_k)
    Skp = kp.shape[1]
    n_blocks = Skp // block_k

    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, D)
    kf = kp.astype(jnp.float32).reshape(B, Skp, Hkv, D)
    vf = vp.astype(jnp.float32).reshape(B, Skp, Hkv, D)
    dof = dout.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    of = out.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    # delta_i = sum_d dout_i * out_i  (rowsum trick)
    delta = jnp.sum(dof * of, axis=-1)  # (B, Sq, Hkv, G)

    q_pos = q_offset + jnp.arange(Sq)
    valid_len = kv_len

    kb_all = kf.reshape(B, n_blocks, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb_all = vf.reshape(B, n_blocks, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)

    def body(dq_acc, blk):
        kb, vb, j = blk
        k_pos = j * block_k + jnp.arange(block_k)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kb)
        mask = k_pos[None, :] < valid_len
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if sliding_window:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - sliding_window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # (B, Sq, Hkv, G, bk)
        p = jnp.where(mask[None, :, None, None, :], p, 0.0)
        dv_b = jnp.einsum("bqhgk,bqhgd->bkhd", p, dof)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", dof, vb)
        ds = p * (dp - delta[..., None])  # (B, Sq, Hkv, G, bk)
        dq_new = dq_acc + jnp.einsum("bqhgk,bkhd->bqhgd", ds, kb)
        dq_new = pin(dq_new.reshape(B, Sq, Hkv * G, D)
                     ).reshape(B, Sq, Hkv, G, D)
        dk_b = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qf)
        return dq_new, (dk_b, dv_b)

    dq0 = pin(jnp.zeros((B, Sq, Hkv * G, D), jnp.float32)
              ).reshape(B, Sq, Hkv, G, D)
    dq, (dk_blocks, dv_blocks) = lax.scan(
        body, dq0, (kb_all, vb_all, jnp.arange(n_blocks)))
    dq = (dq * scale).reshape(B, Sq, Hq, D).astype(q.dtype)
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(B, Skp, Hkv, D)[:, :Sk]
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(B, Skp, Hkv, D)[:, :Sk]
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.lru_cache(maxsize=None)
def _flash_vjp_factory(causal, sliding_window, block_k, scale,
                       carry_constrain):
    import numpy as _np
    _f0 = lambda: _np.zeros((), jax.dtypes.float0)

    @jax.custom_vjp
    def fa(q, k, v, q_offset, kv_len):
        out, _ = _flash_fwd_inner(
            q, k, v, q_offset, kv_len, causal=causal,
            sliding_window=sliding_window, block_k=block_k, scale=scale,
            carry_constrain=carry_constrain)
        return out

    def fa_fwd(q, k, v, q_offset, kv_len):
        out, lse = _flash_fwd_inner(
            q, k, v, q_offset, kv_len, causal=causal,
            sliding_window=sliding_window, block_k=block_k, scale=scale,
            carry_constrain=carry_constrain)
        return out, (q, k, v, q_offset, kv_len, out, lse)

    def fa_bwd(res, dout):
        q, k, v, q_offset, kv_len, out, lse = res
        dq, dk, dv = _flash_bwd_inner(
            q, k, v, q_offset, kv_len, out, lse, dout, causal=causal,
            sliding_window=sliding_window, block_k=block_k, scale=scale,
            carry_constrain=carry_constrain)
        return dq, dk, dv, _f0(), _f0()

    fa.defvjp(fa_fwd, fa_bwd)
    return fa


def attention_naive(q, k, v, *, causal=True, q_offset=0, kv_len=None,
                    sliding_window: int = 0, scale=None):
    """O(Sq*Sk) direct attention — oracle for the oracle (tiny shapes only)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if kv_len is not None:
        mask = mask & (k_pos[None, :] < kv_len)
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if sliding_window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - sliding_window)
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) — chunked scan.
# ---------------------------------------------------------------------------


def _segsum(x: jax.Array) -> jax.Array:
    """x: (..., Q). Returns (..., Q, Q) with out[..., i, j] = sum_{j<s<=i} x[s]
    for j <= i, -inf otherwise (log of the decay matrix L)."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_ref(
    x: jax.Array,   # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)   (post-softplus, positive)
    A: jax.Array,   # (H,)        (negative)
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    *,
    chunk: int = 128,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
    return_state: bool = False,
    chunk_states: Optional[Callable] = None,
    chunk_output: Optional[Callable] = None,
):
    """Chunked SSD: y[t] = C[t] . h[t],
    h[t] = exp(dt[t] A) h[t-1] + dt[t] B[t] x[t].

    Heads H are grouped over G B/C groups (H % G == 0) and kept as a
    (G, K = H/G) pair of axes: B and C are never repeated over the K
    heads of a group. Every contraction is one two-operand dot, with the
    per-position scale factors folded into one operand first; a product
    of more operands is lowered by XLA as an elementwise loop, not on the
    matrix unit.

    ``chunk_states`` and ``chunk_output``, where given, compute in place
    of the XLA code here each chunk's state, ``chunk_states(x, dt, dA_cs,
    Bm)`` -> (B, C, G, K, P, N) float32, and each chunk's output, its
    intra-chunk and inter-chunk parts, ``chunk_output(x, dt, dA_cs, Bm, Cm,
    h_prev)`` -> (B, C, Q, G, K, P) in x's dtype; on the padded inputs,
    the within-chunk cumulative sums (B, C, Q, G, K) of dt * A and the
    state entering each chunk (``ssd_intra``).
    """
    B_, S, H, P = x.shape
    _, _, G, N = Bm.shape
    assert H % G == 0
    K = H // G
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = x.shape[1]
    C_ = Sp // chunk

    f32 = jnp.float32
    xc = x.astype(f32).reshape(B_, C_, chunk, G, K, P)
    dtc = dt.astype(f32).reshape(B_, C_, chunk, G, K)
    Bc = Bm.astype(f32).reshape(B_, C_, chunk, G, N)
    Cc = Cm.astype(f32).reshape(B_, C_, chunk, G, N)
    Af = A.astype(f32).reshape(G, K)

    dA = dtc * Af                                 # (B, C, Q, G, K)
    dA_cs = jnp.cumsum(dA, axis=2)                # cumulative within chunk
    xdt = xc * dtc[..., None]                     # (B, C, Q, G, K, P)

    # ---- intra-chunk (diagonal blocks) ----
    if chunk_output is None:
        L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 4, 2)))  # (B,C,G,K,Q,Q)
        # scores: C[l] . B[s] per head group
        CB = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc)   # (B, C, G, Q, Q)
        M = CB[:, :, :, None] * L                        # decay-weighted
        y_intra = jnp.einsum("bcgkls,bcsgkp->bclgkp", M, xdt)

    # ---- chunk states ----
    if chunk_states is None:
        decay_to_end = jnp.exp(dA_cs[:, :, -1:] - dA_cs)  # (B, C, Q, G, K)
        states = jnp.einsum("bcsgn,bcsgkp->bcgkpn",
                            Bc, xdt * decay_to_end[..., None])
    else:
        states = chunk_states(x, dt, dA_cs, Bm)

    # ---- inter-chunk recurrence ----
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))  # (B, C, G, K)

    def scan_fn(h, inp):
        st, dec = inp  # st: (B, G, K, P, N), dec: (B, G, K)
        h_new = h * dec[..., None, None] + st
        return h_new, h

    h0 = (jnp.zeros((B_, G, K, P, N), f32) if init_state is None
          else init_state.astype(f32).reshape(B_, G, K, P, N))
    states_t = jnp.moveaxis(states, 1, 0)             # (C, B, G, K, P, N)
    decay_t = jnp.moveaxis(chunk_decay, 1, 0)         # (C, B, G, K)
    h_last, h_prev = lax.scan(scan_fn, h0, (states_t, decay_t))
    # (B, C, G, K, P, N) state BEFORE chunk
    h_prev = jnp.moveaxis(h_prev, 0, 1)

    if chunk_output is None:
        # ---- inter-chunk output ----
        y_inter = (jnp.einsum("bclgn,bcgkpn->bclgkp", Cc, h_prev)
                   * jnp.exp(dA_cs)[..., None])
        y = y_intra + y_inter
    else:
        y = chunk_output(x, dt, dA_cs, Bm, Cm, h_prev)
    y = y.reshape(B_, Sp, H, P)[:, :S]
    y = y.astype(x.dtype)
    if return_state:
        return y, h_last.reshape(B_, H, P, N)
    return y


def ssd_decode_ref(
    x: jax.Array,   # (B, H, P)  one token
    dt: jax.Array,  # (B, H)
    A: jax.Array,   # (H,)
    Bm: jax.Array,  # (B, G, N)
    Cm: jax.Array,  # (B, G, N)
    h: jax.Array,   # (B, H, P, N) state
):
    f32 = jnp.float32
    B_, H, P = x.shape
    G = Bm.shape[1]
    HG = H // G
    dA = jnp.exp(dt.astype(f32) * A.astype(f32)[None, :])  # (B, H)
    Br = jnp.repeat(Bm.astype(f32), HG, axis=1)  # (B, H, N)
    Cr = jnp.repeat(Cm.astype(f32), HG, axis=1)
    h_new = h * dA[:, :, None, None] + (
        dt.astype(f32)[:, :, None, None]
        * x.astype(f32)[:, :, :, None]
        * Br[:, :, None, :]
    )
    y = jnp.einsum("bhpn,bhn->bhp", h_new, Cr)
    return y.astype(x.dtype), h_new


def ssd_sequential_ref(x, dt, A, Bm, Cm, *, init_state=None):
    """Token-by-token recurrence — oracle for ssd_ref (tiny shapes only)."""
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    h = (jnp.zeros((B_, H, P, N), jnp.float32) if init_state is None
         else init_state.astype(jnp.float32))
    ys = []
    for t in range(S):
        y, h = ssd_decode_ref(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], h)
        ys.append(y)
    return jnp.stack(ys, axis=1).astype(x.dtype), h


# ---------------------------------------------------------------------------
# Cross entropy: direct (oracle) and vocab-blockwise (never materializes the
# full logit row per token beyond one block).
# ---------------------------------------------------------------------------


def cross_entropy_direct_ref(
    hidden: jax.Array,    # (T, D)
    w_vocab: jax.Array,   # (V, D)
    targets: jax.Array,   # (T,) int32
    valid: Optional[jax.Array] = None,  # (T,) bool
):
    logits = jnp.einsum("td,vd->tv", hidden.astype(jnp.float32),
                        w_vocab.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    nll = lse - tgt
    if valid is not None:
        nll = jnp.where(valid, nll, 0.0)
        return nll.sum() / jnp.maximum(valid.sum(), 1)
    return nll.mean()


def cross_entropy_blockwise_ref(
    hidden: jax.Array,
    w_vocab: jax.Array,
    targets: jax.Array,
    valid: Optional[jax.Array] = None,
    *,
    block_v: int = 2048,
):
    T, D = hidden.shape
    V = w_vocab.shape[0]
    block_v = min(block_v, V)
    wp, _ = _pad_to(w_vocab, 0, block_v)
    Vp = wp.shape[0]
    nb = Vp // block_v
    hf = hidden.astype(jnp.float32)
    wb = wp.astype(jnp.float32).reshape(nb, block_v, D)

    def body(carry, blk):
        m, l, tgt = carry
        w_blk, j = blk
        logits = hf @ w_blk.T  # (T, block_v)
        vids = j * block_v + jnp.arange(block_v)
        logits = jnp.where(vids[None, :] < V, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        l_new = (l * jnp.exp(m - m_new)
                 + jnp.exp(logits - m_new[:, None]).sum(-1))
        hit = vids[None, :] == targets[:, None]
        tgt_new = tgt + jnp.where(hit, logits, 0.0).sum(-1) \
            + jnp.where(hit.any(-1), 0.0, 0.0)
        return (m_new, l_new, tgt_new), None

    m0 = jnp.full((T,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((T,), jnp.float32)
    t0 = jnp.zeros((T,), jnp.float32)
    (m, l, tgt), _ = lax.scan(body, (m0, l0, t0), (wb, jnp.arange(nb)))
    nll = (m + jnp.log(l)) - tgt
    if valid is not None:
        nll = jnp.where(valid, nll, 0.0)
        return nll.sum() / jnp.maximum(valid.sum(), 1)
    return nll.mean()
