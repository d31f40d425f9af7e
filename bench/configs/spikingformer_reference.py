"""Plain jax.numpy reference of Spikingformer BPTT training, for both
configurations in this directory.

It imports nothing of the program under test. It reads the program's
parameter layout (the nested dict of ``tokenizer`` stages, depth-stacked
``blocks`` and ``head``) as one reads a checkpoint format, and follows the
equations the configuration files state:

* tokenizer: per stage, a 3x3 stride-2 SAME convolution, BatchNorm over
  (T*B, H, W) with batch statistics, LIF over T;
* block: X' = LIF(X); Q, K, V = LIF(BN(X' W)); A = (Q K^T) V per head,
  times ``attn_scale``; X += BN(LIF(A) Wz); X += BN(LIF(BN(LIF(X) Wa)) Wb);
* head: mean over T and tokens, a linear layer, mean cross-entropy;
* LIF: U_t = alpha U_{t-1} (1 - S_{t-1}) + X_t, S_t = [U_t >= th_fire],
  with the rectangular surrogate grad_scale * [th_lo < U_t < th_hi] and the
  reset path kept in the gradient;
* AdamW with global-norm clipping, linear warm-up then cosine decay, and
  decoupled decay on every stored leaf of rank 2 or more.

Each block and tokenizer stage is rematerialised, which changes no number
and lets the reference run at the timed batch on one chip. ``dtype``
sets the precision of everything (parameters, moments, activations,
statistics): float32, at the matmul precision the configuration states
(XLA's default), is the reference; bfloat16 is the control that the
comparison must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _fire(u, th_fire, th_lo, th_hi, grad_scale):
    return (u >= th_fire).astype(u.dtype)


def _fire_fwd(u, th_fire, th_lo, th_hi, grad_scale):
    return _fire(u, th_fire, th_lo, th_hi, grad_scale), u


def _fire_bwd(th_fire, th_lo, th_hi, grad_scale, u, g):
    mask = ((u > th_lo) & (u < th_hi)).astype(g.dtype) * grad_scale
    return (g * mask,)


_fire.defvjp(_fire_fwd, _fire_bwd)


def lif(x, c):
    """Spikes of an LIF neuron over the leading time axis of ``x``."""
    def step(carry, xt):
        u, s = carry
        u = c["alpha"] * u * (1 - s) + xt
        s = _fire(u, c["th_fire"], c["th_lo"], c["th_hi"], c["grad_scale"])
        return (u, s), s

    zero = jnp.zeros_like(x[0])
    return jax.lax.scan(step, (zero, zero), x)[1]


def batchnorm(x, p, s, m):
    """Training-mode BatchNorm over every axis but the last, with the
    paper's variance E[x^2] - E[x]^2 (E2ATST eq. 14-15); also the running
    statistics ``s`` blended with the batch's."""
    axes = tuple(range(x.ndim - 1))
    mu = jnp.mean(x, axes)
    var = jnp.maximum(jnp.mean(jnp.square(x), axes) - jnp.square(mu), 0)
    y = p["gamma"] * ((x - mu) / jnp.sqrt(var + m["eps"])) + p["beta"]
    k = m["momentum"]
    return y, {"mean": k * s["mean"] + (1 - k) * mu,
               "var": k * s["var"] + (1 - k) * var}


def _stage(p, s, x, m):
    t, b, h, w, ch = x.shape
    y = jax.lax.conv_general_dilated(
        x.reshape(t * b, h, w, ch), p["conv"]["w"], window_strides=(2, 2),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y, bn = batchnorm(y, p["bn"], s["bn"], m["bn"])
    return lif(y.reshape(t, b, *y.shape[1:]), m["lif"]), {"bn": bn}


def _linear_bn(p, s, x, m):
    y, bn = batchnorm(x @ p["linear"]["w"], p["bn"], s["bn"], m["bn"])
    return y, {"bn": bn}


def _block(p, s, x, m):
    c, heads = m["lif"], m["model"]["n_heads"]
    t, b, n, d = x.shape
    new = {"pssa": {}, "smlp": {}}
    xs = lif(x, c)
    qkv = []
    for k in "qkv":
        y, new["pssa"][k] = _linear_bn(p["pssa"][k], s["pssa"][k], xs, m)
        qkv.append(lif(y, c))
    split = lambda a: a.reshape(t, b, n, heads, d // heads)  # noqa: E731
    q, k, v = (split(a) for a in qkv)
    attn = jnp.einsum("tbnhe,tbmhe->tbhnm", q, k)
    out = jnp.einsum("tbhnm,tbmhe->tbnhe", attn, v).reshape(t, b, n, d)
    out = lif(out * m["model"]["attn_scale"], c)
    z, new["pssa"]["z"] = _linear_bn(p["pssa"]["z"], s["pssa"]["z"], out, m)
    x = x + z
    h, new["smlp"]["a"] = _linear_bn(p["smlp"]["a"], s["smlp"]["a"],
                                     lif(x, c), m)
    y, new["smlp"]["b"] = _linear_bn(p["smlp"]["b"], s["smlp"]["b"],
                                     lif(h, c), m)
    return x + y, new


def logits_of(params, state, images, m):
    """(B, classes) logits of static images (B, H, W, C), repeated over T,
    and the BN running statistics after this batch (the state layout of
    the parameters' BN leaves)."""
    x = jnp.broadcast_to(images[None], (m["model"]["time_steps"],)
                         + images.shape)
    stage = jax.checkpoint(lambda p, s, x: _stage(p, s, x, m))
    tok = []
    for p, s in zip(params["tokenizer"], state["tokenizer"]):
        x, new = stage(p, s, x)
        tok.append(new)
    t, b = x.shape[:2]
    x = x.reshape(t, b, -1, x.shape[-1])
    block = jax.checkpoint(lambda x, ps: _block(ps[0], ps[1], x, m))
    x, blocks = jax.lax.scan(block, x, (params["blocks"], state["blocks"]))
    feat = jnp.mean(x, axis=(0, 2))
    logits = feat @ params["head"]["w"] + params["head"]["b"]
    return logits, {"tokenizer": tok, "blocks": blocks}


def loss_of(params, state, images, labels, m):
    logits, new = logits_of(params, state, images, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1)), new


def learning_rate(o, step):
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0, 1)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos)


def adamw(params, grads, opt, o):
    """One AdamW step on the program's optimizer-state layout
    (``{"m", "v", "step", ...}``)."""
    step = opt["step"] + 1
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    lr = learning_rate(o, step.astype(jnp.float32))
    sf = step.astype(jnp.float32)
    bc1, bc2 = 1 - o["beta1"] ** sf, 1 - o["beta2"] ** sf

    def upd(p, g, mo, v):
        dt = p.dtype
        g = g * clip.astype(dt)
        mo = o["beta1"] * mo + (1 - o["beta1"]) * g
        v = o["beta2"] * v + (1 - o["beta2"]) * jnp.square(g)
        u = (mo / bc1.astype(dt)) / (jnp.sqrt(v / bc2.astype(dt)) + o["eps"])
        if p.ndim >= 2:
            u = u + o["weight_decay"] * p
        return p - lr.astype(dt) * u, mo, v

    out = jax.tree.map(upd, params, grads, opt["m"], opt["v"])
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda _, o_: o_[i], params, out)
    return pick(0), {**opt, "m": pick(1), "v": pick(2), "step": step}


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
