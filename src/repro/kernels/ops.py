"""jit-able dispatch wrappers: Pallas TPU kernels vs pure-jnp XLA refs.

The model zoo calls these entry points exclusively.  ``use_pallas=False``
(CPU smoke tests, the 512-device dry-run) routes to ``ref.py``;
``use_pallas=True`` routes to the Pallas kernels, which Mosaic compiles on
the TPU and which run in interpret mode where the default backend is the
CPU (``kernels.resolve_interpret``).

Each entry point runs under a ``jax.named_scope`` of ``SCOPES``, so that
the reference and the kernel carry the same name in the compiled
program's ``op_name`` metadata, which a profiler trace shows as ``tf_op``.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref as _ref
from repro.kernels import ssd_intra

SCOPES = ("embed", "norm", "proj", "conv", "ssd", "attention", "mlp",
          "logits_ce", "adamw", "shared_block", "adapter")
"""The ``jax.named_scope`` names on the layers of the train step.  A scope
names ops and changes nothing else in the compiled program.

* ``embed``: ``models.layers.embed`` (the token lookup).
* ``norm``: ``rmsnorm`` here, so every RMSNorm; in ``models.mamba2`` also
  the gate ``y * silu(z)`` of the gated norm.
* ``proj``: the six projections of ``models.mamba2.block_fwd`` (``w_z``,
  ``w_x``, ``w_B``, ``w_C``, ``w_dt``, ``w_out``).
* ``conv``: ``models.mamba2._causal_conv``.
* ``ssd``: ``ssd`` and ``ssd_decode`` here; in ``models.mamba2`` also the
  skip term ``D * x`` added to their output.
* ``attention``: ``models.layers.attention`` (projections, and
  ``flash_attention`` here).
* ``mlp``: ``models.layers.mlp`` and ``models.layers.moe_block``.
* ``logits_ce``: the logits and cross entropy of ``train.loss.lm_loss``.
* ``adamw``: ``optim.adamw.adamw_update`` (clipping and the update).
* ``shared_block``: a hybrid layer's shared transformer block in
  ``models.hybrid``: the concatenation with the embeddings and the
  per-application linear map (its norms, attention and MLP keep their
  own scopes, which are innermost).
* ``adapter``: the low-rank adapter products of ``models.layers.mlp``.

Under ``jax.grad`` and ``jax.checkpoint`` an op's path reads ``jvp(...)``
in the forward pass, ``transpose(jvp(...))`` in the backward pass, and
``.../rematted_computation/...`` in the recomputed forward pass.
"""


def scoped(name: str):
    """Decorator: run the function under ``jax.named_scope(name)``, a name
    of ``SCOPES``."""
    assert name in SCOPES, name

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


@scoped("norm")
def rmsnorm(x, w, *, eps: float = 1e-6, use_pallas: bool = False):
    """Over the last axis of x; ``w`` has the shape of x's trailing axes
    (one weight vector, or one per group of a grouped norm)."""
    if use_pallas:
        if w.ndim != 1:
            raise ValueError("the Pallas RMSNorm takes one weight vector")
        from repro.kernels.rmsnorm import rmsnorm_pallas
        return rmsnorm_pallas(x, w, eps=eps)
    return _ref.rmsnorm_ref(x, w, eps)


@scoped("attention")
def flash_attention(q, k, v, *, causal: bool = True, q_offset=0, kv_len=None,
                    sliding_window: int = 0, block_k: int = 512,
                    scale=None, use_pallas: bool = False,
                    carry_constrain=None, custom_vjp: bool = True):
    """``scale``: the softmax scale; None -> head_dim ** -0.5."""
    if use_pallas:
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(
            q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
            sliding_window=sliding_window, scale=scale)
    return _ref.flash_attention_ref(
        q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
        sliding_window=sliding_window, block_k=block_k, scale=scale,
        carry_constrain=carry_constrain, custom_vjp=custom_vjp)


@scoped("ssd")
def ssd(x, dt, A, Bm, Cm, *, chunk: int = 128, init_state=None,
        return_state: bool = False, use_pallas: bool = False):
    """The chunked scan.  Where the program runs on a TPU and the shapes
    allow it (``ssd_intra.kernel_takes``), each chunk's state and output
    come from the Pallas kernels of ``ssd_intra`` and the recurrence over
    the chunks from ``ssd_ref``'s XLA code; elsewhere ``ssd_ref`` whole.
    ``use_pallas`` takes the forward-only sequential kernel
    ``ssd_pallas``."""
    if use_pallas:
        from repro.kernels.ssd_scan import ssd_pallas
        return ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                          init_state=init_state, return_state=return_state)
    xla = functools.partial(_ref.ssd_ref, chunk=chunk,
                            return_state=return_state)
    kb = ssd_intra.kernel_takes(x.shape, Bm.shape[2], Bm.shape[3], chunk)
    if kb is None:
        return xla(x, dt, A, Bm, Cm, init_state=init_state)
    tpu = functools.partial(ssd_intra.ssd_kernel_path, chunk=chunk, kb=kb,
                            return_state=return_state, interpret=False)
    return jax.lax.platform_dependent(
        x, dt, A, Bm, Cm, init_state,
        tpu=lambda *a: tpu(*a[:5], init_state=a[5]),
        default=lambda *a: xla(*a[:5], init_state=a[5]))


@scoped("ssd")
def ssd_decode(x, dt, A, Bm, Cm, h):
    """Single-token SSD recurrence (decode fast path)."""
    return _ref.ssd_decode_ref(x, dt, A, Bm, Cm, h)


def cross_entropy(hidden, w_vocab, targets, valid=None, *,
                  mode: str = "direct", block_v: int = 4096,
                  use_pallas: bool = False):
    if use_pallas:
        from repro.kernels.cross_entropy import cross_entropy_pallas
        return cross_entropy_pallas(hidden, w_vocab, targets, valid,
                                    block_v=block_v)
    if mode == "blockwise":
        return _ref.cross_entropy_blockwise_ref(hidden, w_vocab, targets,
                                                valid, block_v=block_v)
    return _ref.cross_entropy_direct_ref(hidden, w_vocab, targets, valid)
