"""The training reference: a plain float32 model of one family (its
``embed``, ``layer`` and ``logits``) trained by a plain AdamW, layer by
layer so that it fits next to nothing else on one chip.

A step runs the forward pass once, keeping each layer's input, then the
backward pass twice, layer by layer with each layer recomputed: the first
pass sums the squares of the gradient (for clipping by the global norm),
the second applies the update to that layer at once. No full gradient tree
is ever held. Parameters and both Adam moments are float32.

The optimizer is as the configuration states it (``optimizer`` in
``configs/<name>.json``): AdamW with bias correction, clipping by global
norm, decoupled weight decay on the leaves that the stated rule names,
and a linear warm-up followed by a cosine decay to ``floor`` of the peak.
Parameters are kept in the dtype the configuration stores them in: a leaf
stored in bfloat16 is rounded to it after each update (the arithmetic of
the update is float32), since a stored bfloat16 weight cannot take a
change below half its last place.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp

from bench.reference.precision import matmul_for


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def named_leaves(tree) -> Dict[str, jax.Array]:
    return {leaf_name(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def lr_scale(step: int, warmup: int, total: int, floor: float) -> float:
    warm = min(step / max(warmup, 1), 1.0)
    frac = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return warm * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * frac)))


def decays(name: str, stored_ndim: int, rule: str) -> bool:
    """Whether weight decay applies to a leaf, by the configuration's rule.
    ``stored_rank2``: every leaf of rank 2 or more as the parameters are
    stored, layers stacked (so every per-layer leaf, and the embedding and
    head tables, but not the final norm)."""
    if rule == "stored_rank2":
        return stored_ndim >= 2
    raise ValueError(f"unknown weight-decay rule {rule!r}")


def cross_entropy(logits, labels):
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


class TrainReference:
    """``fam`` is a reference module (``reference/<family>.py``)."""

    def __init__(self, fam, cfg, opt: Dict[str, Any], precision: str):
        self.cfg, self.opt = cfg, opt
        mm = matmul_for(precision)

        def one(stacked, i):
            return jax.tree.map(lambda a: a[i], stacked)

        def fwd_layer(stacked, i, h):
            return fam.layer(one(stacked, i), h, cfg, mm)

        def top_loss(top, h, labels):
            return cross_entropy(fam.logits(top, h, cfg, mm), labels)

        def bwd_layer(stacked, i, h, g):
            _, vjp = jax.vjp(lambda lp, x: fam.layer(lp, x, cfg, mm),
                             one(stacked, i), h)
            return vjp(g)

        def sumsq(tree):
            return jax.tree.map(lambda x: jnp.sum(jnp.square(x)), tree)

        def embed_grad(g_table, tokens, g_h):
            return g_table.at[tokens.reshape(-1)].add(
                g_h.reshape(-1, g_h.shape[-1]))

        b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]

        def adam(p, g, m, v, clip, lr, wd, count, bf16):
            g = g * clip
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            upd = (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count))
                                             + eps)
            p = p - lr * (upd + wd * p)
            p = jnp.where(bf16 > 0, p.astype(jnp.bfloat16).astype(p.dtype), p)
            return p, m, v

        def update_layer(sp, sm, sv, i, g, clip, lr, wds, count, bf16s):
            def upd(p, m, v, gl, wd, bf16):
                pn, mn, vn = adam(p[i], gl, m[i], v[i], clip, lr, wd, count,
                                  bf16)
                return p.at[i].set(pn), m.at[i].set(mn), v.at[i].set(vn)
            out = jax.tree.map(upd, sp, sm, sv, g, wds, bf16s)
            return tuple(jax.tree.map(lambda o, k=k: o[k], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
                         for k in range(3))

        def update_top(p, m, v, g, clip, lr, wds, count, bf16s):
            out = jax.tree.map(lambda *a: adam(*a[:4], clip, lr, a[4], count,
                                               a[5]),
                               p, g, m, v, wds, bf16s)
            return tuple(jax.tree.map(lambda o, k=k: o[k], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
                         for k in range(3))

        self.embed = jax.jit(fam.embed)
        self.fwd_layer = jax.jit(fwd_layer)
        self.top_vg = jax.jit(jax.value_and_grad(top_loss, argnums=(0, 1)))
        self.bwd_layer = jax.jit(bwd_layer)
        self.sumsq = jax.jit(sumsq)
        self.embed_grad = jax.jit(embed_grad, donate_argnums=(0,))
        self.update_layer = jax.jit(update_layer, donate_argnums=(0, 1, 2))
        self.update_top = jax.jit(update_top, donate_argnums=(0, 1, 2))

    def run(self, make_params: Callable[[], Any],
            batches: List[Dict[str, Any]], steps: List[int],
            stored: Any) -> Dict[str, Any]:
        """Train the parameters ``make_params()`` returns (the program's
        layout, float32) on ``batches``, the s-th at schedule step
        ``steps[s]``. Returns the losses, the norms of the first clipped
        gradient and of each leaf's change, all keyed by leaf name.
        ``make_params`` is called again at the end for the starting point,
        so that no second copy is held while training. ``stored`` is a tree
        like the parameters of the dtypes they are stored in."""
        opt, cfg = self.opt, self.cfg
        n_layers = cfg.n_layers
        params = make_params()
        top = {k: v for k, v in params.items() if k != "layers"}
        lay = params["layers"]
        del params
        zeros = lambda t: jax.tree.map(jnp.zeros_like, t)  # noqa: E731
        m_top, v_top, m_lay, v_lay = zeros(top), zeros(top), zeros(lay), \
            zeros(lay)
        rule = opt["decay"]
        wd_top = jax.tree_util.tree_map_with_path(
            lambda p, x: opt["weight_decay"] if decays(
                leaf_name(p), x.ndim, rule) else 0.0, top)
        wd_lay = jax.tree_util.tree_map_with_path(
            lambda p, x: opt["weight_decay"] if decays(
                "layers/" + leaf_name(p), x.ndim, rule) else 0.0, lay)
        bf16 = jax.tree.map(lambda d: float(d == jnp.bfloat16), stored)
        bf16_top = {k: v for k, v in bf16.items() if k != "layers"}
        bf16_lay = bf16["layers"]
        losses, first_grad = [], None
        for s, (batch, step) in enumerate(zip(batches, steps)):
            tokens = jnp.asarray(batch["tokens"])
            labels = jnp.asarray(batch["labels"])
            hs = [self.embed(top, tokens)]
            for i in range(n_layers):
                hs.append(self.fwd_layer(lay, i, hs[-1]))
            loss, (g_top, g_h) = self.top_vg(top, hs.pop(), labels)
            losses.append(float(loss))
            # pass 1: the global norm
            sq_lay = None
            g = g_h
            for i in reversed(range(n_layers)):
                g_lp, g = self.bwd_layer(lay, i, hs[i], g)
                sq = self.sumsq(g_lp)
                sq_lay = sq if sq_lay is None else jax.tree.map(
                    jnp.add, sq_lay, sq)
            g_top["embed"]["table"] = self.embed_grad(
                g_top["embed"]["table"], tokens, g)
            sq_top = self.sumsq(g_top)
            total = sum(float(x) for x in jax.tree.leaves((sq_top, sq_lay)))
            clip = min(1.0, opt["grad_clip"] / max(math.sqrt(total), 1e-12))
            lr = opt["lr"] * lr_scale(step, opt["warmup_steps"],
                                      opt["total_steps"], opt["floor"])
            count = s + 1
            if s == 0:
                first_grad = {k: clip * math.sqrt(float(x)) for k, x in
                              named_leaves({**sq_top, "layers": sq_lay}
                                           ).items()}
            # pass 2: the update, layer by layer
            g = g_h
            for i in reversed(range(n_layers)):
                g_lp, g = self.bwd_layer(lay, i, hs[i], g)
                lay, m_lay, v_lay = self.update_layer(
                    lay, m_lay, v_lay, i, g_lp, clip, lr, wd_lay, count,
                    bf16_lay)
            top, m_top, v_top = self.update_top(top, m_top, v_top, g_top,
                                                clip, lr, wd_top, count,
                                                bf16_top)
            del hs, g_top, g_h, g
        del m_top, v_top, m_lay, v_lay
        final = named_leaves({**top, "layers": lay})
        p0 = named_leaves(make_params())
        change = {k: float(jnp.linalg.norm((final[k] - p0[k]).ravel()))
                  for k in final}
        return {"losses": losses, "grad": first_grad, "change": change}
