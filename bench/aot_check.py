#!/usr/bin/env python3
"""Compile a configuration's largest programs for a described TPU v5e, with no
chip, and reckon its device memory.

    JAX_PLATFORMS=cpu python bench/aot_check.py bench/configs/<name>.json

Composes the configuration's chains on the host, then compiles the weight
maker, a prefill at ``max_seq`` and the paged decode step at every row bucket
and span the engines can reach, for one chip, and prints each program's
``memory_analysis()`` and the sum the chip must hold: weights, every chain's
page pool, and the largest program's temporaries.  Nothing runs; a compile that passes is not a
chip run.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(path: str) -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH.parent / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import model, warmup
    from repro.core import Server, compose_best_effort
    from repro.models import Model
    from repro.serving import service_spec_for
    from repro.serving.engine import PagedChainEngine
    from repro.serving.kv_cache import PagedCache

    conf = json.loads(Path(path).read_text())
    m = model.dims(conf)
    cfg = model.model_config(conf)
    mdl = Model(cfg)
    c = conf["cluster"]
    servers = [Server(f"srv{i}", float(c["memory_gb"]), float(c["tau_c_s"]),
                      float(c["tau_p_s"][i % len(c["tau_p_s"])]))
               for i in range(int(c["servers"]))]
    spec = service_spec_for(cfg, max_seq=m["max_seq"])
    _, alloc, _ = compose_best_effort(servers, spec, float(c["compose_rate_rps"]),
                                      float(c["rho_bar"]), tuner=c["tuner"])
    chains = [[list(ch.servers), list(ch.blocks), cap]
              for ch, cap in alloc.sorted_by_rate()]
    page = int(c["page_size"])
    pages_per_slot = m["max_seq"] // page

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    params = jax.tree.map(sds, jax.eval_shape(mdl.init, jax.random.PRNGKey(0)))

    def analysis(compiled):
        ma = compiled.memory_analysis()
        return {"argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes)}

    out = {"chains": chains, "programs": {}}
    pools = []
    for _, _, cap in chains:
        cache = PagedCache(mdl, max(1, int(cap * float(c["oversubscribe"]))),
                           m["max_seq"], page_size=page,
                           total_pages=cap * pages_per_slot, materialize=False)
        leaves = [jax.ShapeDtypeStruct(
            (s.shape[0], cache.total_pages + 1, page, *s.shape[3:]), s.dtype,
            sharding=one) for s, paged in zip(cache._one_specs, cache._paged)]
        pools.append(sum(x.size * x.dtype.itemsize for x in leaves))
    cap = max(ch[2] for ch in chains)
    slots = max(1, int(cap * float(c["oversubscribe"])))
    eng = PagedChainEngine.__new__(PagedChainEngine)
    eng.model = mdl
    eng.cache = cache
    for rows in sorted({warmup.pow2(r) for r in range(1, slots + 1)}):
        span = page
        while span <= m["max_seq"]:
            step = jax.jit(eng._step_impl, donate_argnums=(1,)).lower(
                params, leaves, i32(rows, span // page), i32(rows), i32(rows),
                i32(rows), i32(rows), i32(rows)).compile()
            out["programs"][f"decode_step rows {rows} span {span}"] = \
                analysis(step)
            span *= 2
    buf = jax.tree.map(sds, jax.eval_shape(lambda: cache.prefill_buffer(m["max_seq"])))
    prefill = jax.jit(mdl.prefill).lower(params, buf, {"tokens": i32(1, m["max_seq"])}).compile()
    out["programs"][f"prefill {m['max_seq']}"] = analysis(prefill)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    init = model.make_weights_fn(m).lower(key).compile()
    out["programs"]["make_weights"] = analysis(init)

    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    temps = max(p["temp_bytes"] for p in out["programs"].values())
    out["weights_bytes"] = int(weights)
    out["pool_bytes"] = [int(p) for p in pools]
    out["largest_temp_bytes"] = int(temps)
    out["total_bytes"] = int(weights + sum(pools) + temps)
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1]), indent=1))
