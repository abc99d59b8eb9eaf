"""A configuration file as the program runs it: its ``ModelConfig``, its weights
made on the device from the seed, and the cluster the control plane composes.

The weights are the benchmark's, not the program's: one jitted call draws every
leaf from the seed in the type it is served in, and the reference reads the same
arrays.  The tree is laid out as the program's ``Model.init`` lays it out, which
:func:`check_layout` holds against the program's own shapes.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List

import numpy as np


def dims(conf: dict) -> Dict[str, Any]:
    """The sizes of a configuration file under the names the harness uses."""
    if conf.get("hidden_act") != "silu":
        raise ValueError("only SwiGLU (hidden_act silu) blocks are supported")
    if float(conf.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError("the program rotates the whole head; partial rotary "
                         "is not supported")
    heads = int(conf["num_attention_heads"])
    d = int(conf["hidden_size"])
    return {
        "layers": int(conf["num_hidden_layers"]),
        "d_model": d,
        "heads": heads,
        "kv_heads": int(conf.get("num_key_value_heads", heads)),
        "head_dim": int(conf.get("head_dim", d // heads)),
        "d_ff": int(conf["intermediate_size"]),
        "vocab": int(conf["vocab_size"]),
        "qkv_bias": bool(conf.get("use_qkv_bias", conf.get("attention_bias",
                                                             False))),
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": float(conf.get("rms_norm_eps",
                                   conf.get("layer_norm_eps", 1e-6))),
        "tied": bool(conf.get("tie_word_embeddings", False)),
        "dtype": conf.get("torch_dtype", "bfloat16"),
        "max_seq": int(conf["max_position_embeddings"]),
    }


def model_config(conf: dict):
    from repro.configs.base import ModelConfig

    m = dims(conf)
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=m["layers"],
        d_model=m["d_model"], num_heads=m["heads"], num_kv_heads=m["kv_heads"],
        d_ff=m["d_ff"], vocab_size=m["vocab"], head_dim=m["head_dim"],
        qkv_bias=m["qkv_bias"], rope_theta=m["rope_theta"],
        mlp_type="swiglu", tie_embeddings=m["tied"], dtype=m["dtype"])


def weight_shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """``{path: (shape, init)}`` of every leaf; init is ``("normal", std)``
    or ``("ones_jitter", std)`` (norm weights near 1)."""
    L, D, H, KV, hd, F, V = (m[k] for k in ("layers", "d_model", "heads",
                                            "kv_heads", "head_dim", "d_ff",
                                            "vocab"))
    out = {
        "embed": ((V, D), ("normal", 1.0)),
        "final_norm": ((D,), ("ones_jitter", 0.1)),
        "stages/0/ln1": ((L, D), ("ones_jitter", 0.1)),
        "stages/0/ln2": ((L, D), ("ones_jitter", 0.1)),
        "stages/0/wq": ((L, D, H * hd), ("normal", 1 / math.sqrt(D))),
        "stages/0/wk": ((L, D, KV * hd), ("normal", 1 / math.sqrt(D))),
        "stages/0/wv": ((L, D, KV * hd), ("normal", 1 / math.sqrt(D))),
        "stages/0/wo": ((L, H * hd, D), ("normal", 1 / math.sqrt(H * hd))),
        "stages/0/mlp/w_gate": ((L, D, F), ("normal", 1 / math.sqrt(D))),
        "stages/0/mlp/w_up": ((L, D, F), ("normal", 1 / math.sqrt(D))),
        "stages/0/mlp/w_down": ((L, F, D), ("normal", 1 / math.sqrt(F))),
    }
    if not m["tied"]:
        out["lm_head"] = ((D, V), ("normal", 1 / math.sqrt(D)))
    if m["qkv_bias"]:
        out["stages/0/bq"] = ((L, H * hd), ("normal", 0.1))
        out["stages/0/bk"] = ((L, KV * hd), ("normal", 0.1))
        out["stages/0/bv"] = ((L, KV * hd), ("normal", 0.1))
    return out


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    tree["stages"] = [tree["stages"]["0"]]
    return tree


def seed_key(seed: int):
    """A JAX key from any non-negative seed (``PRNGKey`` truncates past 32
    bits, so the seed is hashed to one 32-bit word first)."""
    import jax

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def make_weights_fn(m: Dict[str, Any]):
    """The jitted weight maker: a key in, every leaf out, in the served dtype."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(m)
    dt = jnp.dtype(m["dtype"])

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (path, (shape, (kind, std))) in zip(keys, sorted(shapes.items())):
            x = jax.random.normal(k, shape, jnp.float32) * std
            if kind == "ones_jitter":
                x = 1.0 + x
            flat[path] = x.astype(dt)
        return _nest(flat)

    return init


def make_weights(m: Dict[str, Any], seed: int):
    """Every leaf drawn from ``seed`` on the default device, in one call."""
    return make_weights_fn(m)(seed_key(seed))


def check_layout(model, weights) -> None:
    """The benchmark's tree has the program's structure, shapes and dtypes."""
    import jax

    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"weight tree {jax.tree.structure(got)} is not the "
                         f"program's {jax.tree.structure(want)}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight leaf {b.shape} {b.dtype} is not the "
                             f"program's {a.shape} {a.dtype}")


def build_orchestrator(conf: dict, cfg, model, weights):
    """The control plane and data plane as ``LivePlane(engine="jax",
    kv_layout="paged")`` builds them: GBP-CR and GCA compose chains over the
    configuration's servers, and JFFC dispatches to ``PagedChainEngine``s."""
    from repro.core import Server
    from repro.serving import Orchestrator, OrchestratorConfig, service_spec_for
    from repro.serving.engine import PagedChainEngine

    c = conf["cluster"]
    max_seq = dims(conf)["max_seq"]
    servers: List = [Server(f"srv{i}", float(c["memory_gb"]), float(c["tau_c_s"]),
                            float(c["tau_p_s"][i % len(c["tau_p_s"])]))
                     for i in range(int(c["servers"]))]
    spec = service_spec_for(cfg, max_seq=max_seq)
    factory = partial(PagedChainEngine, page_size=int(c["page_size"]),
                      oversubscribe=float(c["oversubscribe"]))
    ocfg = OrchestratorConfig(rho_bar=float(c["rho_bar"]), tuner=c["tuner"],
                              max_seq=max_seq, engine_factory=factory)
    return Orchestrator(servers, spec, model, weights,
                        float(c["compose_rate_rps"]), ocfg)
