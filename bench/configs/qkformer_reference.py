"""Plain jax.numpy reference of QKFormer BPTT training (Zhou et al., NeurIPS
2024, arXiv:2403.16552), for the configurations in this directory that name
``"family": "qkformer"``.

It imports nothing of the program under test; the LIF, BatchNorm and AdamW
are the Spikingformer reference's (``spikingformer_reference.py``), used as
they are. It reads the program's parameter layout (``embed``: one patch
embedding per stage; ``stages``: each stage's blocks stacked over depth;
``head``) as one reads a checkpoint format, and follows the equations the
configuration file states:

* SN(x) is the LIF over T; SN_attn the same LIF at the ``lif.attn``
  threshold and surrogate window;
* patch embedding (SPEDS): 3x3 stride-1 SAME convolutions, each followed by
  BatchNorm and, where marked, a 3x3 stride-2 max-pool (padding 1); SN
  after each conv but the last; the last conv's BN output plus a 1x1
  stride-2 convolution + BN of the last two convs' input, then SN. Stage 1:
  c_in -> w/2 (pooled), w/2 -> w (pooled), w -> w. Each later stage:
  w -> 2w, 2w -> 2w (pooled);
* Q-K token attention (every stage but the last): Q = SN(BN(X Wq)),
  K = SN(BN(X Wk)); per head a = SN_attn(sum of Q over the head's
  channels); out = SN(BN((a * K) Wproj));
* spiking self-attention (last stage): Q, K, V = SN(BN(X W));
  A = SN_attn(attn_scale * Q K^T V) per head; out = SN(BN(A Wproj));
* block: X += Attn(X); X += SN(BN(SN(BN(X Wa)) Wb));
* head: mean over T and tokens, a linear layer, mean cross-entropy;
* AdamW with decoupled decay on every stored leaf of rank 2 or more.

Each patch embedding and block is rematerialised, which changes no number
and lets the reference run at the timed batch on one chip. ``dtype`` sets
the precision of everything: float32, at the matmul precision the
configuration states (XLA's default), is the reference; bfloat16 is the
control that the comparison must reject.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.configs.spikingformer_reference import adamw, batchnorm, lif


def _conv_bn(p, s, x, stride, pooled, m):
    """x: (T, B, H, W, C) -> BN(conv(x)) [max-pooled], and the new BN
    statistics."""
    t, b = x.shape[:2]
    y = jax.lax.conv_general_dilated(
        x.reshape(t * b, *x.shape[2:]), p["conv"]["w"],
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y, bn = batchnorm(y, p["bn"], s["bn"], m["bn"])
    if pooled:
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1),
                                  ((0, 0), (1, 1), (1, 1), (0, 0)))
    return y.reshape(t, b, *y.shape[1:]), {"bn": bn}


def _embed(p, s, x, pooled, m):
    """One patch embedding; ``pooled`` marks the convs followed by a
    max-pool."""
    new = {"conv": []}
    for j, flag in enumerate(pooled):
        if j == len(pooled) - 2:
            skip = x
        y, bn = _conv_bn(p["conv"][j], s["conv"][j], x, 1, flag, m)
        new["conv"].append(bn)
        if j < len(pooled) - 1:
            x = lif(y, m["lif"])
    r, new["shortcut"] = _conv_bn(p["shortcut"], s["shortcut"], skip, 2,
                                  False, m)
    return lif(y + r, m["lif"]), new


def _snbn(p, s, x, m):
    y, bn = batchnorm(x @ p["linear"]["w"], p["bn"], s["bn"], m["bn"])
    return lif(y, m["lif"]), {"bn": bn}


def _attn_lif(m):
    return {**m["lif"], **m["lif"]["attn"]}


def _qkta(p, s, x, m):
    heads = m["model"]["n_heads"]
    t, b, n, d = x.shape
    new = {}
    q, new["q"] = _snbn(p["q"], s["q"], x, m)
    k, new["k"] = _snbn(p["k"], s["k"], x, m)
    a = lif(q.reshape(t, b, n, heads, d // heads).sum(-1), _attn_lif(m))
    xk = (a[..., None] * k.reshape(t, b, n, heads, d // heads)).reshape(
        t, b, n, d)
    y, new["proj"] = _snbn(p["proj"], s["proj"], xk, m)
    return y, new


def _ssa(p, s, x, m):
    heads = m["model"]["n_heads"]
    t, b, n, d = x.shape
    new, qkv = {}, []
    for name in "qkv":
        y, new[name] = _snbn(p[name], s[name], x, m)
        qkv.append(y.reshape(t, b, n, heads, d // heads))
    q, k, v = qkv
    attn = jnp.einsum("tbnhe,tbmhe->tbhnm", q, k)
    out = jnp.einsum("tbhnm,tbmhe->tbnhe", attn, v).reshape(t, b, n, d)
    a = lif(out * m["model"]["attn_scale"], _attn_lif(m))
    y, new["proj"] = _snbn(p["proj"], s["proj"], a, m)
    return y, new


def _block(p, s, x, attn, m):
    a, s_attn = attn(p["attn"], s["attn"], x, m)
    x = x + a
    h, s_a = _snbn(p["mlp"]["a"], s["mlp"]["a"], x, m)
    y, s_b = _snbn(p["mlp"]["b"], s["mlp"]["b"], h, m)
    return x + y, {"attn": s_attn, "mlp": {"a": s_a, "b": s_b}}


def logits_of(params, state, images, m):
    """(B, classes) logits of static images (B, H, W, C), repeated over T,
    and the BN running statistics after this batch (the state layout of
    the parameters' BN leaves)."""
    x = jnp.broadcast_to(images[None], (m["model"]["time_steps"],)
                         + images.shape)
    stages = len(m["model"]["widths"])
    embed, blocks = [], []
    for i in range(stages):
        pooled = (True, True, False) if i == 0 else (False, True)
        step = jax.checkpoint(
            lambda p, s, x, pooled=pooled: _embed(p, s, x, pooled, m))
        x, new = step(params["embed"][i], state["embed"][i], x)
        embed.append(new)
        t, b, h, w, c = x.shape
        x = x.reshape(t, b, h * w, c)
        attn = _qkta if i < stages - 1 else _ssa
        block = jax.checkpoint(
            lambda x, ps, attn=attn: _block(ps[0], ps[1], x, attn, m))
        x, new = jax.lax.scan(block, x, (params["stages"][i]["blocks"],
                                         state["stages"][i]["blocks"]))
        blocks.append({"blocks": new})
        if i < stages - 1:
            x = x.reshape(t, b, h, w, c)
    feat = jnp.mean(x, axis=(0, 2))
    logits = feat @ params["head"]["w"] + params["head"]["b"]
    return logits, {"embed": embed, "stages": blocks}


def loss_of(params, state, images, labels, m):
    logits, new = logits_of(params, state, images, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1)), new


def make_step(m: dict, dtype=jnp.float32):
    """A train step with the program's signature, ``(params, state, opt,
    images, labels) -> (params, state, opt, metrics)``, computed wholly in
    ``dtype``; ``state`` holds the BN running statistics. Leaves are stored
    back in the caller's dtypes."""
    o = m["optimizer"]

    def step(params, state, opt, images, labels):
        cast = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: a.astype(dtype), tree)
        p, mo, v = cast(params), cast(opt["m"]), cast(opt["v"])
        (loss, new_state), grads = jax.value_and_grad(loss_of, has_aux=True)(
            p, cast(state), images.astype(dtype), labels, m)
        p, new = adamw(p, grads, {**opt, "m": mo, "v": v}, o)
        back = lambda tree, like: jax.tree.map(  # noqa: E731
            lambda a, b: a.astype(b.dtype), tree, like)
        new = {**new, "m": back(new["m"], opt["m"]),
               "v": back(new["v"], opt["v"])}
        metrics = {"loss": loss.astype(jnp.float32),
                   "nonfinite": jnp.zeros((), jnp.float32)}
        return back(p, params), back(new_state, state), new, metrics

    return step
