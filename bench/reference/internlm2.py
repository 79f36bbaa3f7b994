"""Plain InternLM2 forward (arXiv:2403.17297): jax.numpy, float32, highest
matmul precision, no cache, no kernels, no batching tricks.

Per layer: ``x += Wo·attn(RoPE(Wq·n1), RoPE(Wk·n1), Wv·n1)`` and
``x += Wdown·(silu(Wgate·n2) * Wup·n2)`` with ``n = RMSNorm(x)``;
grouped-query causal attention, rotary embedding on the two halves of
each head, a final RMSNorm and an untied output head.

It takes its weights from the family module it is given
(``bench/families/internlm2.py``: the benchmark's own seeded generator,
one layer at a time), never from the program under test.

``control=True`` runs the same equations in int8 instead: weights per
output channel and activations per token, symmetric, with int32
accumulation, and keys and values per token and head.  That is the
precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import seed_key

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(F32)


def _rope(x, positions, theta):
    """x: (T, H, D); rotate the first and second halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = positions[:, None].astype(F32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _q8(x, axis):
    """Symmetric int8 along ``axis``: (int8 values, float32 scales)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _matmul(x, w, control: bool):
    """x: (..., K) float32, w: (K, N) bfloat16."""
    w = w.astype(F32)
    if not control:
        return x @ w
    xq, xs = _q8(x, -1)
    wq, ws = _q8(w, 0)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(F32) * xs * ws


def _attention(q, k, v, length, control: bool):
    """One sequence. q: (T, H, D), k/v: (T, KV, D); causal, masked past
    ``length``."""
    t, h, dh = q.shape
    kvh = k.shape[1]
    if control:
        kq, ks = _q8(k, -1)
        vq, vs = _q8(v, -1)
        k, v = kq.astype(F32) * ks, vq.astype(F32) * vs
    qg = q.reshape(t, kvh, h // kvh, dh)
    s = jnp.einsum("tkgd,skd->kgts", qg, k) / jnp.sqrt(F32(dh))
    pos = jnp.arange(t)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgts,skd->tkgd", p, v).reshape(t, h * dh)


@functools.partial(jax.jit, static_argnames=("m", "control", "family"))
def _layer(key, layer, x, lengths, *, m, control, family):
    """x: (N, T, d) float32 residual stream of N sequences; ``m`` the
    model sizes as a sorted tuple of items (hashable, so static)."""
    m = dict(m)
    w = family.layer_weights(key, layer, m)
    h, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    n, t, d = x.shape
    dh = d // h
    pos = jnp.arange(t)

    def attn_one(args):
        xs, length = args
        a = _rmsnorm(xs, w["ln1"], eps)
        q = _rope(_matmul(a, w["wq"], control).reshape(t, h, dh), pos, theta)
        k = _rope(_matmul(a, w["wk"], control).reshape(t, kvh, dh), pos, theta)
        v = _matmul(a, w["wv"], control).reshape(t, kvh, dh)
        return _matmul(_attention(q, k, v, length, control), w["wo"], control)

    x = x + jax.lax.map(attn_one, (x, lengths))

    def mlp_one(xs):
        a = _rmsnorm(xs, w["ln2"], eps)
        g = jax.nn.silu(_matmul(a, w["w_gate"], control))
        return _matmul(g * _matmul(a, w["w_up"], control), w["w_down"],
                       control)

    return x + jax.lax.map(mlp_one, x)


@functools.partial(jax.jit, static_argnames=("m", "family"))
def _embed(key, tokens, *, m, family):
    return family.outer_weights(key, dict(m))["embed"][tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("m", "control", "family"))
def _gaps(key, x_ref, x_ctl, tokens, *, m, control, family):
    """Per sequence and position, the gap by which the token judged there
    lies below the reference's best logit.  The token at position ``i+1``
    is predicted at position ``i``; with ``control`` the token judged is
    the control's own first choice instead of the one that follows."""
    m = dict(m)
    o = family.outer_weights(key, m)
    eps = m["rms_norm_eps"]

    def one(args):
        xr, xc, tok = args
        ref = _rmsnorm(xr, o["final_norm"], eps) @ o["unembed"].astype(F32)
        nxt = jnp.concatenate([tok[1:], tok[:1]])
        if control:
            ctl = _matmul(_rmsnorm(xc, o["final_norm"], eps), o["unembed"],
                          True)
            nxt = jnp.argmax(ctl, axis=-1)
        judged = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - judged

    return jax.lax.map(one, (x_ref, x_ctl, tokens))


def _hidden(key, m, tokens, lengths, control, family):
    mt = tuple(sorted(m.items()))
    x = _embed(key, tokens, m=mt, family=family)
    for layer in range(m["num_hidden_layers"]):
        x = _layer(key, jnp.int32(layer), x, lengths, m=mt, control=control,
                   family=family)
    return x


def logit_gaps(seed: int, m: Dict, sequences: Sequence[Tuple[List[int],
               List[int]]], length: int, *, family, control: bool = False,
               host: Optional[Sequence[Sequence[bool]]] = None) -> Dict:
    """Judge each (prompt, served tokens) pair: at every served token, the
    gap (in logits) by which it lies below the reference's best token.
    Returns the widest gap per sequence, the mean gap over all served
    tokens and their number, and the same mean and number over the served
    tokens that ``host`` marks (one flag per served token: made by the
    host tier).  With ``control`` the judged tokens are those the int8
    forward puts first instead.  All sequences are padded to ``length``
    positions, so one program serves every call.  ``family`` is the
    configuration's family module, whose ``layer_weights`` and
    ``outer_weights`` regenerate the weights from ``seed``."""
    with jax.default_matmul_precision("highest"):
        key = seed_key(seed)
        n = len(sequences)
        tokens = np.zeros((n, length), np.int32)
        served = np.zeros((n, length), bool)
        on_host = np.zeros((n, length), bool)
        for i, (prompt, out) in enumerate(sequences):
            seq = list(prompt) + list(out)
            if len(seq) > length:
                raise ValueError(f"sequence of {len(seq)} tokens exceeds "
                                 f"the reference length {length}")
            tokens[i, : len(seq)] = seq
            # served token j sits at len(prompt) + j, predicted one earlier
            first = len(prompt) - 1
            served[i, first: first + len(out)] = True
            if host is not None and len(out):
                flags = np.asarray(host[i], bool)
                if len(flags) != len(out):
                    raise ValueError("one host flag per served token")
                on_host[i, first: first + len(out)] = flags
        tok = jnp.asarray(tokens)
        lens = jnp.asarray([len(p) + len(o) for p, o in sequences], jnp.int32)
        x_ref = _hidden(key, m, tok, lens, False, family)
        x_ctl = _hidden(key, m, tok, lens, True, family) if control else x_ref
        gap = np.asarray(_gaps(key, x_ref, x_ctl, tok,
                               m=tuple(sorted(m.items())), control=control,
                               family=family))
        widest = np.where(served, gap, -np.inf).max(axis=1)
        judged = int(served.sum())
        host_judged = int(on_host.sum())
        return {"widest": widest,
                "mean": float(gap[served].sum()) / max(judged, 1),
                "judged": judged,
                "host_mean": float(gap[on_host].sum()) / max(host_judged, 1),
                "host_judged": host_judged}
