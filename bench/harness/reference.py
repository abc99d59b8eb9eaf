"""The plain reference: the configuration's forward pass in float32.

Written from the model's published equations as the configuration file states
them (RMSNorm, rotary position embedding on whole heads in the half-split
layout, grouped-query attention, SwiGLU MLP, untied head) in straightforward
``jax.numpy`` at ``Precision.HIGHEST``.  It imports nothing of the program and
reads only the weights the benchmark made.  It runs one sequence and one layer
at a time, each padded to ``max_seq`` positions so that one program serves
every request: causal attention leaves the positions before the padding as
they are.

``quant="fp8"`` is the control: every matrix stored in float8 (e4m3, one scale
per matrix), the precision below the configuration's bfloat16.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def _fp8(w):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w)) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _as_f32(w, quant: Optional[str]):
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    return _fp8(w) if quant == "fp8" else w


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _rope(x, theta):
    import jax.numpy as jnp

    S, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class Reference:
    """``gaps(weights, tokens, ...)`` for one configuration (``dims``)."""

    def __init__(self, m: Dict, quant: Optional[str] = None):
        import jax

        self.m = m
        self.quant = quant
        self._embed = jax.jit(self._embed_impl)
        self._layer = jax.jit(self._layer_impl)
        self._logits_top = jax.jit(self._top_impl)
        self._gaps = jax.jit(self._gaps_impl)

    # -- pieces ----------------------------------------------------------------
    def _embed_impl(self, table, tokens):
        return _as_f32(table, self.quant)[tokens]

    def _layer_impl(self, x, stage, l):
        import jax
        import jax.numpy as jnp

        hp = jax.lax.Precision.HIGHEST
        m, q8 = self.m, self.quant
        H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
        S = x.shape[0]
        p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, l, 0, keepdims=False), stage)
        mm = lambda a, w: jnp.matmul(a, _as_f32(w, q8), precision=hp)
        h = _rms(x, p["ln1"].astype(jnp.float32), m["norm_eps"])
        q, k, v = mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"])
        if m["qkv_bias"]:
            q = q + p["bq"].astype(jnp.float32)
            k = k + p["bk"].astype(jnp.float32)
            v = v + p["bv"].astype(jnp.float32)
        q = _rope(q.reshape(S, H, hd), m["rope_theta"])
        k = _rope(k.reshape(S, KV, hd), m["rope_theta"])
        v = v.reshape(S, KV, hd)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=hp) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                       precision=hp)
        x = x + mm(a.reshape(S, H * hd), p["wo"])
        h = _rms(x, p["ln2"].astype(jnp.float32), m["norm_eps"])
        g = jax.nn.silu(mm(h, p["mlp"]["w_gate"])) * mm(h, p["mlp"]["w_up"])
        return x + mm(g, p["mlp"]["w_down"])

    def _head(self, x, final_norm, head):
        import jax
        import jax.numpy as jnp

        h = _rms(x, final_norm.astype(jnp.float32), self.m["norm_eps"])
        return jnp.matmul(h, _as_f32(head, self.quant),
                          precision=jax.lax.Precision.HIGHEST)

    def _top_impl(self, x, final_norm, head):
        import jax.numpy as jnp

        return jnp.argmax(self._head(x, final_norm, head), axis=-1)

    def _gaps_impl(self, x, final_norm, head, nxt, other):
        """Per position: how far below the row's best lie the logits of the
        next token and of ``other`` (the control's first choice)."""
        import jax.numpy as jnp

        logits = self._head(x, final_norm, head)
        best = jnp.max(logits, axis=-1)
        pick = lambda t: jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return best - pick(nxt), best - pick(other)

    # -- one sequence ------------------------------------------------------------
    def hidden(self, weights, tokens: np.ndarray):
        import jax.numpy as jnp

        pad = np.zeros((self.m["max_seq"],), np.int32)
        pad[: len(tokens)] = tokens
        x = self._embed(weights["embed"], jnp.asarray(pad))
        stage = weights["stages"][0]
        for l in range(self.m["layers"]):
            x = self._layer(x, stage, jnp.int32(l))
        return x

    def _head_weights(self, weights):
        head = weights["embed"].T if self.m["tied"] else weights["lm_head"]
        return weights["final_norm"], head

    def top(self, weights, tokens: np.ndarray) -> np.ndarray:
        """First choice at every position (the control's tokens)."""
        x = self.hidden(weights, tokens)
        return np.asarray(self._logits_top(x, *self._head_weights(weights)))

    def gaps(self, weights, tokens: np.ndarray, first: int,
             other: Optional[np.ndarray] = None):
        """Gaps of ``tokens[first:]`` (the served tokens) and of ``other`` at
        the positions that predicted them."""
        import jax.numpy as jnp

        S = self.m["max_seq"]
        nxt = np.zeros((S,), np.int32)
        nxt[: len(tokens) - 1] = tokens[1:]
        oth = np.zeros((S,), np.int32) if other is None else np.asarray(
            other, np.int32)
        x = self.hidden(weights, tokens)
        g, go = self._gaps(x, *self._head_weights(weights), jnp.asarray(nxt),
                           jnp.asarray(oth))
        pos = slice(first - 1, len(tokens) - 1)
        return np.asarray(g)[pos], np.asarray(go)[pos]


def served_gaps(m: Dict, weights, samples: List, control: bool = False):
    """``(program gaps, control gaps or None)`` over the sampled requests:
    each sample is ``(prompt, served tokens)``.  Gaps are in logits of the
    float32 reference."""
    ref = Reference(m)
    ctl = Reference(m, quant="fp8") if control else None
    prog, ctrl = [], []
    for prompt, served in samples:
        tokens = np.concatenate([prompt, np.asarray(served, np.int32)])
        other = None
        if ctl is not None:
            other = ctl.top(weights, tokens)
        g, go = ref.gaps(weights, tokens, len(prompt), other)
        prog.append(g)
        if ctl is not None:
            ctrl.append(go)
    prog = np.concatenate(prog)
    return prog, (np.concatenate(ctrl) if control else None)
