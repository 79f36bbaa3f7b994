"""Plain float32 forward of the test family ``wide_heads``: InternLM2's
equations with heads of ``head_dim``, which need not be
``hidden_size / num_attention_heads``.  No cache, no kernels, no int8
control.  Weights come from the family module it is given."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import seed_key

F32 = jnp.float32


def head_dim(m):
    return m["head_dim"]


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(F32)


def _rope(x, theta):
    """x: (T, H, D); rotate the first and second halves of each head."""
    t, dh = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _block(x, length, w, m):
    """One sequence's layer. x: (T, d)."""
    t = x.shape[0]
    dh = head_dim(m)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    a = _rmsnorm(x, w["ln1"], eps)
    q = _rope((a @ w["wq"].astype(F32)).reshape(t, -1, dh), theta)
    k = _rope((a @ w["wk"].astype(F32)).reshape(t, -1, dh), theta)
    v = (a @ w["wv"].astype(F32)).reshape(t, -1, dh)
    h, kvh = q.shape[1], k.shape[1]
    qg = q.reshape(t, kvh, h // kvh, dh)
    s = jnp.einsum("tkgd,skd->kgts", qg, k) / jnp.sqrt(F32(dh))
    pos = jnp.arange(t)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v).reshape(t, h * dh)
    x = x + o @ w["wo"].astype(F32)
    a = _rmsnorm(x, w["ln2"], eps)
    g = jax.nn.silu(a @ w["w_gate"].astype(F32)) * (a @ w["w_up"].astype(F32))
    return x + g @ w["w_down"].astype(F32)


def logit_gaps(seed, m, sequences, length, *, family, control=False,
               host=None):
    """As ``bench/reference/internlm2.py``'s ``logit_gaps``: the gap by
    which each served token lies below the reference's best logit; the
    widest per sequence, the mean, and the mean over host-tier tokens."""
    if control:
        raise ValueError("the wide_heads test reference has no control")
    with jax.default_matmul_precision("highest"):
        key = seed_key(seed)
        n = len(sequences)
        tokens = np.zeros((n, length), np.int32)
        served = np.zeros((n, length), bool)
        on_host = np.zeros((n, length), bool)
        for i, (prompt, out) in enumerate(sequences):
            seq = list(prompt) + list(out)
            tokens[i, : len(seq)] = seq
            first = len(prompt) - 1
            served[i, first: first + len(out)] = True
            if host is not None and len(out):
                on_host[i, first: first + len(out)] = np.asarray(host[i], bool)
        lens = jnp.asarray([len(p) + len(o) for p, o in sequences], jnp.int32)
        o = family.outer_weights(key, m)
        x = o["embed"][jnp.asarray(tokens)].astype(F32)
        for layer in range(m["num_hidden_layers"]):
            w = family.layer_weights(key, layer, m)
            x = jax.vmap(lambda xs, ln: _block(xs, ln, w, m))(x, lens)
        logits = _rmsnorm(x, o["final_norm"], m["rms_norm_eps"]) \
            @ o["unembed"].astype(F32)
        nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        judged = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
        gap = np.asarray(jnp.max(logits, axis=-1) - judged)
    widest = np.where(served, gap, -np.inf).max(axis=1)
    n_judged, n_host = int(served.sum()), int(on_host.sum())
    return {"widest": widest,
            "mean": float(gap[served].sum()) / max(n_judged, 1),
            "judged": n_judged,
            "host_mean": float(gap[on_host].sum()) / max(n_host, 1),
            "host_judged": n_host}
