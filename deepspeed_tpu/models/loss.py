"""Loss functions for the model zoo.

Includes the vocab-parallel-safe LM cross-entropy (role of reference
deepspeed/sequence/cross_entropy.py — there vocab-parallel logits require a
custom all-reduce softmax; under GSPMD the same einsum/softmax shards
correctly from the logits' sharding, so one implementation serves both).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..utils.annotations import device_scope

IGNORE_INDEX = -100

#: token rows per chunk of the streaming cross-entropy; 0 (default) =
#: dense fp32 path. Chunking bounds the fp32 logit transients to
#: [chunk, V] instead of [B*S, V] — an OOM escape hatch for huge-vocab /
#: long-seq configs. Measured ~4% slower end-to-end on v5e (the scan
#: serializes against XLA's overlap), so it is opt-in, not the default.
#: Settable via the DS_TPU_CE_CHUNK env var (re-read at every trace, so
#: setting it after import works and it always wins) or programmatically
#: via this module attribute (used when the env var is unset). Either way
#: the value is captured at TRACE time: changing it affects newly traced
#: programs only — JAX caches compiled train steps.
CE_CHUNK = int(os.environ.get("DS_TPU_CE_CHUNK", "0"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _nll_logz(logits2d: jax.Array, labels1d: jax.Array, chunk: int):
    """Per-token (nll, logz) in fp32 from [N, V] bf16 logits, streamed in
    [chunk, V] pieces so the full fp32 logits (and, in the backward, the
    full fp32 dlogits) are never materialized — the role of the reference's
    fused softmax-cross-entropy kernels. Masked rows (label < 0) get 0.
    A non-divisible tail (N % chunk rows) runs as one short static slice,
    so the chunk never degrades and no padded copy of the logits is made."""
    (nll, logz), _ = _nll_logz_fwd(logits2d, labels1d, chunk)
    return nll, logz


def _chunk_starts(N: int, chunk: int) -> jax.Array:
    return jnp.arange(0, N, chunk, dtype=jnp.int32)


def _fwd_piece(lg, lb):
    l32 = lg.astype(jnp.float32)
    mask = lb >= 0
    lz = jax.nn.logsumexp(l32, axis=-1)
    true = jnp.take_along_axis(l32, jnp.where(mask, lb, 0)[:, None],
                               axis=-1)[:, 0]
    return (lz - true) * mask, lz * mask


def _nll_logz_fwd(logits2d, labels1d, chunk):
    N, V = logits2d.shape
    Nm = (N // chunk) * chunk                    # bulk, tail handled apart

    def body(_, start):
        lg = jax.lax.dynamic_slice_in_dim(logits2d, start, chunk)
        lb = jax.lax.dynamic_slice_in_dim(labels1d, start, chunk)
        return None, _fwd_piece(lg, lb)

    _, (nll, logz) = jax.lax.scan(body, None, _chunk_starts(Nm, chunk))
    nll, logz = nll.reshape(Nm), logz.reshape(Nm)
    if Nm != N:
        tn, tz = _fwd_piece(logits2d[Nm:], labels1d[Nm:])
        nll = jnp.concatenate([nll, tn])
        logz = jnp.concatenate([logz, tz])
    return (nll, logz), (logits2d, labels1d)


def _bwd_piece(lg, lb, gn, gz, V):
    l32 = lg.astype(jnp.float32)
    mask = lb >= 0
    p = jax.nn.softmax(l32, axis=-1)
    d = p * ((gn + gz) * mask)[:, None]
    onehot = jax.nn.one_hot(jnp.where(mask, lb, 0), V, dtype=jnp.float32)
    return (d - onehot * (gn * mask)[:, None]).astype(lg.dtype)


def _nll_logz_bwd(chunk, res, grads):
    logits2d, labels1d = res
    dnll, dlogz = grads                                   # [N] fp32 each
    N, V = logits2d.shape
    Nm = (N // chunk) * chunk

    def body(_, start):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, chunk)
        return None, _bwd_piece(sl(logits2d), sl(labels1d), sl(dnll),
                                sl(dlogz), V)

    _, dchunks = jax.lax.scan(body, None, _chunk_starts(Nm, chunk))
    d = dchunks.reshape(Nm, V)
    if Nm != N:
        tail = _bwd_piece(logits2d[Nm:], labels1d[Nm:], dnll[Nm:],
                          dlogz[Nm:], V)
        d = jnp.concatenate([d, tail])
    return d, None


_nll_logz.defvjp(_nll_logz_fwd, _nll_logz_bwd)


def cross_entropy_lm(logits: jax.Array, labels: jax.Array,
                     ignore_index: int = IGNORE_INDEX,
                     z_loss_weight: float = 0.0) -> jax.Array:
    """Mean next-token cross entropy. ``logits`` [B,S,V], ``labels`` [B,S]
    already shifted by the caller (labels[t] is the target for logits[t])."""
    import math

    V = logits.shape[-1]
    N = math.prod(logits.shape[:-1])
    mask = (labels != ignore_index)
    denom = jnp.maximum(jnp.sum(mask), 1)
    env = os.environ.get("DS_TPU_CE_CHUNK")
    ce_chunk = int(env) if env is not None else CE_CHUNK
    if ce_chunk:
        chunk = min(ce_chunk, N)
        lab = jnp.where(mask, labels, -1).reshape(N)
        nll, logz = _nll_logz(logits.reshape(N, V), lab, chunk)
        return _masked_mean_loss(nll, logz, denom, z_loss_weight)
    logits = logits.astype(jnp.float32)
    safe_labels = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - true_logit) * mask
    loss = jnp.sum(nll) / denom
    if z_loss_weight:
        loss = loss + z_loss_weight * jnp.sum(jnp.square(logz) * mask) / denom
    return loss


def _masked_mean_loss(nll, logz, denom, z_loss_weight):
    """Shared CE reduction: mean of pre-masked per-token nll (+ z-loss on
    pre-masked logz) — the single place the denom/z-loss semantics live
    for the chunked AND fused head paths."""
    loss = jnp.sum(nll) / denom
    if z_loss_weight:
        loss = loss + z_loss_weight * jnp.sum(jnp.square(logz)) / denom
    return loss


# ---------------------------------------------------------------------------
# Fused LM head + cross entropy: the unembedding matmul and the softmax
# CE run together in an online-logsumexp scan over VOCAB chunks, so the
# [B*S, V] logits tensor never exists — in any precision. This is the
# step beyond CE_CHUNK (which streams rows but still needs the full
# logits input): for llama-class vocabs at long sequence the logits are
# the single largest activation, and this removes them from both the
# forward and the backward (the reference's fused softmax-CE kernels +
# vocab-parallel cross entropy play the same memory role). Opt-in via
# DS_TPU_FUSED_HEAD_CHUNK (vocab columns per chunk) — the engine's
# default loss uses it automatically when the model runs with
# ``return_hidden`` support.
# ---------------------------------------------------------------------------

NEG_INF_F32 = float(jnp.finfo(jnp.float32).min)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_nll_logz(x2d, w, bias, labels1d, vchunk: int, w_is_ve: bool):
    """Per-token (nll, logz) from hidden states and the head weight.
    x2d [N, E]; w [V, E] (tied embedding) or [E, V] (unembed);
    bias [V] or None; labels [N] (< 0 = masked). V pads to vchunk."""
    (out, _) = _fused_fwd(x2d, w, bias, labels1d, vchunk, w_is_ve)
    return out


def _head_chunk(x2d, w, bias, c0, vchunk, w_is_ve, V):
    """One vocab chunk's logits in fp32, plus the EFFECTIVE start.
    dynamic_slice clamps starts near the end, so the tail chunk reads
    [V - vchunk, V); columns outside the LOGICAL range [c0, min(c0+vchunk,
    V)) are masked to -inf — they were already covered by earlier chunks.
    Returns (lg [N, vchunk], c0_eff)."""
    c0_eff = jnp.minimum(c0, V - vchunk)
    if w_is_ve:
        wc = jax.lax.dynamic_slice_in_dim(w, c0_eff, vchunk, axis=0)
        lg = jax.lax.dot_general(x2d, wc, (((1,), (1,)), ((), ())))
    else:
        wc = jax.lax.dynamic_slice_in_dim(w, c0_eff, vchunk, axis=1)
        lg = x2d @ wc
    lg = lg.astype(jnp.float32)
    if bias is not None:
        lg = lg + jax.lax.dynamic_slice_in_dim(
            bias, c0_eff, vchunk).astype(jnp.float32)[None, :]
    pos = c0_eff + jnp.arange(vchunk)
    valid = (pos >= c0) & (pos < V)
    return jnp.where(valid[None, :], lg, jnp.float32(NEG_INF_F32)), c0_eff


def _fused_fwd(x2d, w, bias, labels1d, vchunk, w_is_ve):
    N = x2d.shape[0]
    V = w.shape[0] if w_is_ve else w.shape[1]
    starts = jnp.arange(0, V, vchunk, dtype=jnp.int32)
    mask = labels1d >= 0
    safe = jnp.where(mask, labels1d, 0)

    def body(carry, c0):
        m, l, true = carry
        lg, c0_eff = _head_chunk(x2d, w, bias, c0, vchunk, w_is_ve, V)
        m_new = jnp.maximum(m, jnp.max(lg, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(lg - m_new[:, None]), axis=-1)
        in_chunk = (safe >= c0) & (safe < c0 + vchunk)
        idx = jnp.clip(safe - c0_eff, 0, vchunk - 1)
        true = true + jnp.where(
            in_chunk, jnp.take_along_axis(lg, idx[:, None], axis=1)[:, 0],
            0.0)
        return (m_new, l, true), None

    init = (jnp.full((N,), NEG_INF_F32), jnp.zeros((N,), jnp.float32),
            jnp.zeros((N,), jnp.float32))
    (m, l, true), _ = jax.lax.scan(body, init, starts)
    logz = m + jnp.log(l)
    nll = (logz - true) * mask
    return (nll, logz * mask), (x2d, w, bias, labels1d, logz)


def _fused_bwd(vchunk, w_is_ve, res, grads):
    x2d, w, bias, labels1d, logz = res
    dnll, dlogz = grads                                   # [N] fp32
    N, E = x2d.shape
    V = w.shape[0] if w_is_ve else w.shape[1]
    w_axis = 0 if w_is_ve else 1
    starts = jnp.arange(0, V, vchunk, dtype=jnp.int32)
    mask = labels1d >= 0
    safe = jnp.where(mask, labels1d, 0)
    coeff = ((dnll + dlogz) * mask)
    gn = dnll * mask

    def body(carry, c0):
        dx, dw, db = carry
        lg, c0_eff = _head_chunk(x2d, w, bias, c0, vchunk, w_is_ve, V)
        p = jnp.exp(lg - logz[:, None])   # softmax chunk (0 at -inf cols)
        d = p * coeff[:, None]
        in_chunk = (safe >= c0) & (safe < c0 + vchunk)
        onehot = jax.nn.one_hot(jnp.where(in_chunk, safe - c0_eff, vchunk),
                                vchunk, dtype=jnp.float32)
        d = d - onehot * gn[:, None]                      # [N, Vc] fp32
        d16 = d.astype(x2d.dtype)
        wc = jax.lax.dynamic_slice_in_dim(w, c0_eff, vchunk, axis=w_axis)
        if w_is_ve:
            dx = dx + jax.lax.dot_general(
                d16, wc, (((1,), (0,)), ((), ()))).astype(jnp.float32)
            dwc = jax.lax.dot_general(                    # [Vc, E]
                d16, x2d, (((0,), (0,)), ((), ())))
        else:
            dx = dx + (d16 @ wc.T).astype(jnp.float32)
            dwc = jax.lax.dot_general(                    # [E, Vc]
                x2d, d16, (((0,), (0,)), ((), ())))
        # read-add-write: the clamped tail chunk overlaps earlier columns
        # (their d is 0 there, but the slot must accumulate, not overwrite)
        cur = jax.lax.dynamic_slice_in_dim(dw, c0_eff, vchunk, axis=w_axis)
        dw = jax.lax.dynamic_update_slice_in_dim(dw, cur + dwc, c0_eff,
                                                 axis=w_axis)
        if bias is not None:
            dbc = jnp.sum(d, axis=0)
            curb = jax.lax.dynamic_slice_in_dim(db, c0_eff, vchunk)
            db = jax.lax.dynamic_update_slice_in_dim(db, curb + dbc, c0_eff,
                                                     axis=0)
        return (dx, dw, db), None

    dx0 = jnp.zeros((N, E), jnp.float32)
    dw0 = jnp.zeros(w.shape, jnp.float32)
    db0 = None if bias is None else jnp.zeros((V,), jnp.float32)
    (dx, dw, db), _ = jax.lax.scan(body, (dx0, dw0, db0), starts)
    return (dx.astype(x2d.dtype), dw.astype(w.dtype),
            None if db is None else db.astype(bias.dtype), None)


_fused_nll_logz.defvjp(_fused_fwd, _fused_bwd)


def fused_lm_head_loss(hidden, w, labels, *, bias=None,
                       ignore_index: int = IGNORE_INDEX,
                       z_loss_weight: float = 0.0,
                       w_is_ve: bool = True,
                       vchunk: int | None = None) -> jax.Array:
    """Mean next-token CE straight from hidden states [B, S, E] and the
    head weight — no logits tensor. ``w_is_ve``: w is the tied embedding
    [V, E]; else the unembed [E, V]."""
    import math

    if vchunk is None:
        vchunk = int(os.environ.get("DS_TPU_FUSED_HEAD_CHUNK", "8192"))
    E = hidden.shape[-1]
    N = math.prod(hidden.shape[:-1])
    V = w.shape[0] if w_is_ve else w.shape[1]
    vchunk = min(vchunk, V)
    mask = (labels != ignore_index)
    denom = jnp.maximum(jnp.sum(mask), 1)
    lab = jnp.where(mask, labels, -1).reshape(N)
    nll, logz = _fused_nll_logz(hidden.reshape(N, E), w, bias, lab,
                                vchunk, w_is_ve)
    return _masked_mean_loss(nll, logz, denom, z_loss_weight)


def _train_mode_kwargs(batch: dict) -> dict:
    """The engine injects '_train_rng' (one key per optimizer step) into
    training batches — its presence switches the model to train mode:
    deterministic=False with dropout/gating streams derived from the key."""
    rng = batch.get("_train_rng")
    if rng is None:
        return {}
    return {"deterministic": False,
            "rngs": {"dropout": jax.random.fold_in(rng, 0),
                     "gating": jax.random.fold_in(rng, 1)}}


def lm_loss_fn(model, params, batch, deterministic: bool = True):
    """Default engine loss: causal LM on {'input_ids', 'labels'} batches.
    Adds any aux losses the model sowed (MoE balance/z losses).
    DS_TPU_FUSED_HEAD_CHUNK=<vocab cols> routes through the fused
    vocab-chunked head loss — no [B,S,V] logits tensor."""
    input_ids = batch["input_ids"]
    labels = batch.get("labels")
    if labels is None:
        # next-token shift as roll+where, NOT slice+concat: with the seq
        # dim sharded over the 'seq' axis (Ulysses), some XLA versions
        # miscompile concatenate(x[:, 1:], fill) on the sharded dim (the
        # halo exchange drops the fill column — observed on jaxlib
        # 0.4.36 CPU: the ignore mask silently covered zero positions and
        # the loss went NaN). roll lowers to a collective-permute, which
        # is correct on every version in range.
        S = input_ids.shape[1]
        labels = jnp.where(jnp.arange(S)[None, :] < S - 1,
                           jnp.roll(input_ids, -1, axis=1), IGNORE_INDEX)
    kwargs = {"deterministic": deterministic} | _train_mode_kwargs(batch)
    env = os.environ.get("DS_TPU_FUSED_HEAD_CHUNK")
    vchunk = int(env) if env else 0
    if vchunk > 0 and hasattr(model, "config"):
        cfg = model.config
        hidden, variables = model.apply({"params": params}, input_ids,
                                        return_hidden=True,
                                        mutable=["losses"], **kwargs)
        if cfg.tie_embeddings:
            w, w_is_ve = params["embed"].astype(cfg.dtype), True
        else:
            w, w_is_ve = params["unembed"].astype(cfg.dtype), False
        bias = params["unembed_b"] if getattr(cfg, "unembed_bias", False) \
            else None
        with device_scope("head_loss"):
            loss = fused_lm_head_loss(hidden, w, labels, bias=bias,
                                      w_is_ve=w_is_ve, vchunk=vchunk)
    else:
        out, variables = model.apply({"params": params}, input_ids,
                                     mutable=["losses"], **kwargs)
        with device_scope("head_loss"):
            loss = cross_entropy_lm(out, labels)
    for leaf in jax.tree.leaves(variables.get("losses", {})):
        loss = loss + jnp.sum(leaf)
    return loss


def mlm_loss_fn(model, params, batch, deterministic: bool = True):
    """Masked-LM loss for bidirectional encoders (bert family — role of the
    reference's BingBertSquad/BERT pretraining path, tests/model/).

    Batch: {'input_ids' [B,S] with [MASK] already substituted,
    'labels' [B,S] = original ids at masked positions, IGNORE_INDEX
    elsewhere, optional 'attention_mask' [B,S] (1 = real token),
    optional 'token_type_ids' [B,S]}.
    """
    labels = batch["labels"]  # MLM labels are never derivable by shifting
    kwargs = {"deterministic": deterministic} | _train_mode_kwargs(batch)
    out, variables = model.apply(
        {"params": params}, batch["input_ids"],
        attn_mask=batch.get("attention_mask"),
        token_type_ids=batch.get("token_type_ids"),
        mutable=["losses"], **kwargs)
    loss = cross_entropy_lm(out, labels)
    for leaf in jax.tree.leaves(variables.get("losses", {})):
        loss = loss + jnp.sum(leaf)
    return loss
