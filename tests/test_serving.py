"""Serving engine + orchestrator: generation correctness, JFFC dispatch,
failover, elasticity, straggler feedback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import Server
from repro.models import Model
from repro.serving import (
    ChainEngine,
    Orchestrator,
    OrchestratorConfig,
    Request,
    State,
    service_spec_for,
    tau_estimates,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = get("stablelm-1.6b").reduced(num_layers=2, vocab_size=128,
                                       attn_chunk_threshold=1 << 30)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def greedy_rollout(model, params, prompt, n_new):
    """Oracle: re-run the full forward for every generated token."""
    toks = list(prompt)
    for _ in range(n_new):
        logits = model.forward_train(params, {"tokens": jnp.asarray([toks])})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def _mk_request(rid, prompt_len, n_new, seed=0):
    rng = np.random.default_rng(seed + rid)
    return Request(rid=rid, prompt=rng.integers(1, 100, prompt_len).astype(np.int32),
                   max_new_tokens=n_new)


def test_engine_generates_same_as_oracle(tiny):
    cfg, model, params = tiny
    from repro.core.chains import Chain

    eng = ChainEngine(model, params, Chain(("s0",), (cfg.num_layers,), 1.0),
                      capacity=3, max_seq=128)
    reqs = [_mk_request(i, 8 + 3 * i, 6) for i in range(3)]
    for r in reqs:
        assert eng.admit(r)
    while eng.requests:
        eng.step()
    for r in reqs:
        oracle = greedy_rollout(model, params, r.prompt, 6)
        assert r.output == oracle, f"req {r.rid}: {r.output} vs {oracle}"


def test_engine_bucketed_prefill_matches_exact(tiny):
    """Prompt length that is NOT a power of two must still match the oracle
    (exercises the boundary re-decode path)."""
    cfg, model, params = tiny
    from repro.core.chains import Chain

    eng = ChainEngine(model, params, Chain(("s0",), (cfg.num_layers,), 1.0),
                      capacity=1, max_seq=128)
    r = _mk_request(0, 13, 5)     # 13 -> bucket 16
    assert eng.admit(r)
    while eng.requests:
        eng.step()
    assert r.output == greedy_rollout(model, params, r.prompt, 5)


def _orchestrator(tiny, n_servers=4, lam=0.5, mem=None, max_seq=128):
    cfg, model, params = tiny
    spec = service_spec_for(cfg, max_seq=max_seq)
    # memory sized so each server holds the whole reduced model + some slots
    mem = mem if mem is not None else (spec.block_size_gb * cfg.num_layers
                                       + spec.cache_size_gb * cfg.num_layers * 6)
    servers = [Server(f"s{i}", mem, 0.05, 0.02 * (1 + i % 2)) for i in range(n_servers)]
    orch = Orchestrator(servers, spec, model, params, lam,
                        OrchestratorConfig(max_seq=max_seq))
    return orch


def test_orchestrator_serves_batch(tiny):
    orch = _orchestrator(tiny)
    reqs = [_mk_request(i, 8, 4) for i in range(8)]
    for r in reqs:
        orch.submit(r)
    orch.drain()
    assert all(r.state == State.DONE for r in reqs)
    stats = orch.stats()
    assert stats["finished"] == 8 and stats["queued"] == 0
    # outputs must match the oracle regardless of which chain served them
    cfg, model, params = tiny
    for r in reqs[:3]:
        assert r.output == greedy_rollout(model, params, r.prompt, 4)


def test_jffc_prefers_fastest_engine(tiny):
    orch = _orchestrator(tiny)
    rates = [e.chain.rate for e in orch.engines]
    assert rates == sorted(rates, reverse=True)
    r = _mk_request(0, 8, 64)
    orch.submit(r)
    assert r.chain_idx == 0, "first request must land on the fastest chain"


def test_queue_when_capacity_exhausted(tiny):
    orch = _orchestrator(tiny, n_servers=2)
    total_cap = sum(e.capacity for e in orch.engines)
    reqs = [_mk_request(i, 8, 8) for i in range(total_cap + 3)]
    for r in reqs:
        orch.submit(r)
    assert len(orch.queue) == 3
    orch.drain()
    assert all(r.state == State.DONE for r in reqs)


def test_failover_requeues_and_completes(tiny):
    orch = _orchestrator(tiny, n_servers=4)
    reqs = [_mk_request(i, 8, 6) for i in range(6)]
    for r in reqs:
        orch.submit(r)
    # advance a couple of rounds, then kill the server carrying chain 0
    orch.step(); orch.step()
    victim = orch.engines[0].chain.servers[0]
    requeued = orch.fail_server(victim)
    assert victim not in {s for e in orch.engines for s in e.chain.servers}
    orch.drain()
    assert all(r.state == State.DONE for r in reqs)
    # outputs still correct (context preserved across failover)
    cfg, model, params = tiny
    for r in reqs:
        assert r.output == greedy_rollout(model, params, r.prompt, 6), (
            f"req {r.rid} diverged after failover (requeued={requeued})")


def test_elastic_add_server_increases_rate(tiny):
    orch = _orchestrator(tiny, n_servers=2)
    before = orch.allocation.total_rate
    cfg, model, params = tiny
    spec = orch.spec
    mem = spec.block_size_gb * cfg.num_layers + spec.cache_size_gb * cfg.num_layers * 6
    orch.add_server(Server("new", mem, 0.01, 0.005))
    assert orch.allocation.total_rate > before


def test_straggler_feedback_triggers_recompose(tiny):
    orch = _orchestrator(tiny, n_servers=4)
    n0 = orch.recompositions
    sid = orch.engines[0].chain.servers[0]
    for _ in range(12):
        orch.report_tau(sid, 3.0)
    assert orch.tau_scale[sid] > 1.5
    assert orch.recompositions > n0


def test_orchestrator_runs_scripted_scenario(tiny):
    """A core.scenarios timeline (failure -> straggler -> recovery) driven
    through the live orchestrator completes every request."""
    from repro.core import Scenario

    orch = _orchestrator(tiny, n_servers=4)
    victim = orch.engines[0].chain.servers[0]
    victim_server = orch.servers[victim]
    straggler = orch.engines[-1].chain.servers[0]
    scenario = (Scenario(horizon=10.0)
                .fail(2.0, victim)
                .slowdown(4.0, straggler, 1.6)
                .recover(6.0, victim_server))
    reqs = [_mk_request(i, 8, 4) for i in range(6)]
    from repro.api import drive_orchestrator

    summary = drive_orchestrator(orch, scenario, reqs, dt=1.0)
    assert all(r.state == State.DONE for r in reqs)
    assert summary["finished"] == 6 and summary["failed"] == 0
    kinds = [e["kind"] for e in summary["events"]]
    assert kinds == ["fail", "slowdown", "add"]
    assert summary["recompositions"] >= 2     # fail + add at minimum
    # the failed server really left and came back
    assert victim in orch.servers


# ---------------------------------------------------------------------------
# Paged KV cache + continuous batching
# ---------------------------------------------------------------------------

def _paged_engine(tiny, capacity=3, max_seq=128, **kw):
    from repro.core.chains import Chain
    from repro.serving import PagedChainEngine

    cfg, model, params = tiny
    return PagedChainEngine(model, params,
                            Chain(("s0",), (cfg.num_layers,), 1.0),
                            capacity, max_seq, **kw)


def test_paged_engine_generates_same_as_oracle(tiny):
    cfg, model, params = tiny
    eng = _paged_engine(tiny)
    # non-pow2 prompts exercise the boundary fixup; 40 new tokens cross
    # page boundaries (page_size 16) during decode
    reqs = [_mk_request(i, 8 + 3 * i, 40) for i in range(3)]
    for r in reqs:
        assert eng.admit(r)
    while eng.requests:
        eng.step()
    for r in reqs:
        oracle = greedy_rollout(model, params, r.prompt, 40)
        assert r.output == oracle, f"req {r.rid}: {r.output} vs {oracle}"


def test_slotted_paged_greedy_parity(tiny):
    """The layout contract: greedy token streams are bit-identical between
    SlotCache and PagedCache engines, with staggered admissions (continuous
    batching gathers different batch shapes round to round)."""
    from repro.core.chains import Chain
    from repro.serving import ChainEngine, PagedChainEngine

    cfg, model, params = tiny
    chain = Chain(("s0",), (cfg.num_layers,), 1.0)
    outs = {}
    for name, factory in [("slotted", ChainEngine), ("paged", PagedChainEngine)]:
        eng = factory(model, params, chain, 4, 128)
        reqs = [_mk_request(i, 5 + 7 * i, 25, seed=3) for i in range(7)]
        pending = list(reqs)
        while pending or eng.requests:
            while pending and eng.has_free_slot and eng.admit(pending[0]):
                pending.pop(0)
            eng.step()
        outs[name] = [r.output for r in reqs]
    assert outs["slotted"] == outs["paged"]


class _ShapeLog:
    """Wraps an engine's jitted program and records the shapes it is
    called with (the cache's leaves and the tokens)."""

    def __init__(self, fn, log, name):
        self.fn, self.log, self.name = fn, log, name

    def __call__(self, params, cache, tokens, *rest):
        tok = tokens["tokens"] if isinstance(tokens, dict) else tokens
        self.log.append((self.name, tuple(np.shape(tok)), tuple(
            a.shape for a in jax.tree_util.tree_leaves(cache))))
        return self.fn(params, cache, tokens, *rest)

    def clear_cache(self):
        self.fn.clear_cache()


@pytest.mark.parametrize("page_size", [8, 16, 32])
def test_slotted_paged_same_batch_shapes(tiny, page_size):
    """Both layouts hand the model's prefill and decode programs batches of
    the same shapes (kv_span positions, power-of-two rows) at any page
    size; on a TPU a row's logits change with the batch shape, so this is
    what the layouts' stream parity rests on there."""
    from repro.core.chains import Chain
    from repro.serving import PagedChainEngine

    cfg, model, params = tiny
    chain = Chain(("s0",), (cfg.num_layers,), 1.0)
    logs, outs = {}, {}
    for name, factory in [("slotted", ChainEngine), ("paged", PagedChainEngine)]:
        eng = factory(model, params, chain, 2, 64, page_size=page_size)
        log = logs[name] = []
        eng._prefill_jit = _ShapeLog(eng._prefill_jit, log, "prefill")
        eng._decode_jit = _ShapeLog(eng._decode_jit, log, "decode")
        # short prompts first: their batches fit in one page at page 32 and
        # cross pages at page 8
        reqs = [_mk_request(i, n, 12, seed=5)
                for i, n in enumerate((3, 4, 6, 20, 40))]
        pending = list(reqs)
        while pending or eng.requests:
            while pending and eng.has_free_slot and eng.admit(pending[0]):
                pending.pop(0)
            eng.step()
        outs[name] = [r.output for r in reqs]
    assert logs["slotted"] == logs["paged"]
    spans = {leaves[0][2] for kind, _, leaves in logs["paged"]
             if kind == "decode"}
    assert min(spans) == page_size and all(s % page_size == 0 for s in spans)
    assert outs["slotted"] == outs["paged"]


def test_first_token_same_with_and_without_prefill_padding(tiny):
    """The engine's first token does not depend on prefill padding, also
    where the top two logits tie once rounded to bfloat16: a 50-token
    prompt prefilled exactly (max_seq 50) and padded to 64 with the
    boundary re-decode (max_seq 128) pick the same token.  The two paths
    differ in the last bits, and a bfloat16 argmax would break the tie by
    index on that noise; the engines take the head's float32 logits."""
    from repro.core.chains import Chain
    from repro.serving import PagedChainEngine

    cfg, model, params = tiny
    chain = Chain(("s0",), (cfg.num_layers,), 1.0)
    prompt = _mk_request(3, 50, 1).prompt
    exact = ChainEngine(model, params, chain, 1, 50).prefill(prompt)[0][0]
    padded = PagedChainEngine(model, params, chain, 2, 128).prefill(prompt)[0][0]
    assert exact.dtype == padded.dtype == jnp.float32
    top2 = np.argsort(-np.asarray(exact))[:2]
    as_bf16 = np.asarray(exact.astype(jnp.bfloat16))
    assert as_bf16[top2[0]] == as_bf16[top2[1]], "not a bfloat16 tie"
    assert not np.array_equal(np.asarray(exact), np.asarray(padded))
    assert int(jnp.argmax(exact)) == int(jnp.argmax(padded)) == top2[0]


def test_paged_pool_exhaustion_defers_admission(tiny):
    """Oversubscribed slots + a drained page pool: admit refuses (returns
    False) instead of corrupting; freed pages make the request admissible."""
    eng = _paged_engine(tiny, capacity=2, max_seq=128, oversubscribe=3.0)
    # budget: 2 slots * 8 pages = 16 pages over 6 slots; each 50-token
    # prompt takes 4 pages, so the 5th admission finds slots but no pages
    reqs = [_mk_request(i, 50, 2) for i in range(5)]
    admitted = [eng.admit(r) for r in reqs]
    assert admitted == [True, True, True, True, False]
    assert eng.has_free_slot            # a slot is free; pages are not
    assert reqs[4].state == State.QUEUED
    while eng.requests:
        eng.step()
    assert eng.admit(reqs[4])           # pages released -> admissible now
    while eng.requests:
        eng.step()
    cfg, model, params = tiny
    for r in reqs:
        assert r.output == greedy_rollout(model, params, r.prompt, 2)


def test_paged_released_pages_are_reusable(tiny):
    """Admit/complete cycles return every page; the free stack refills and
    reused (dirty) pages decode correctly."""
    eng = _paged_engine(tiny, capacity=2, max_seq=64)
    total = eng.cache.free_pages
    cfg, model, params = tiny
    for round_ in range(3):
        reqs = [_mk_request(10 * round_ + i, 20, 4, seed=round_) for i in range(2)]
        for r in reqs:
            assert eng.admit(r)
        while eng.requests:
            eng.step()
        assert eng.cache.free_pages == total
        for r in reqs:
            assert r.output == greedy_rollout(model, params, r.prompt, 4)


def test_paged_preemption_requeues_youngest(tiny):
    """Page exhaustion during decode preempts the youngest request with its
    generated tokens preserved; the orchestrator-level resubmit completes it
    with oracle-correct output (context re-prefilled)."""
    eng = _paged_engine(tiny, capacity=1, max_seq=128, oversubscribe=3.0)
    # 1 slot of budget = 8 pages; three 30-token prompts (2 pages each) fit,
    # but decoding 40 tokens each needs more pages than the pool holds
    reqs = [_mk_request(i, 30, 40) for i in range(3)]
    for r in reqs:
        assert eng.admit(r)
    preempted = []
    while eng.requests:
        eng.step()
        preempted += eng.take_preempted()
    assert preempted, "pool pressure must preempt"
    assert all(r.state == State.QUEUED and r.retries == 1 for r in preempted)
    # youngest-first victim order: request 0 (oldest) is never preempted
    assert all(r.rid != 0 for r in preempted)
    cfg, model, params = tiny
    for r in preempted:                  # progress preserved in context
        assert list(r.context_tokens[:30]) == list(r.prompt)
        assert len(r.output) > 0
        eng.admit(r)
        while eng.requests:
            eng.step()
    for r in reqs:
        assert r.output == greedy_rollout(model, params, r.prompt, 40)


def test_paged_orchestrator_end_to_end(tiny):
    """Full orchestrator over paged engines: dispatch, preemption drain,
    recompose survival, and the new data-plane metrics."""
    from functools import partial

    from repro.obs import MetricsRegistry
    from repro.serving import PagedChainEngine

    cfg, model, params = tiny
    spec = service_spec_for(cfg, max_seq=128)
    mem = (spec.block_size_gb * cfg.num_layers
           + spec.cache_size_gb * cfg.num_layers * 6)
    servers = [Server(f"s{i}", mem, 0.05, 0.02 * (1 + i % 2)) for i in range(4)]
    orch = Orchestrator(
        servers, spec, model, params, 0.5,
        OrchestratorConfig(max_seq=128,
                           engine_factory=partial(PagedChainEngine,
                                                  page_size=16)))
    orch.metrics = MetricsRegistry()
    reqs = [_mk_request(i, 8, 6) for i in range(8)]
    for r in reqs:
        orch.submit(r)
    orch.step(); orch.step()
    # engines whose (chain, capacity) survive a recompose keep their block
    # tables (same servers -> same composition -> every engine survives)
    before = {tuple(e.chain.servers): (id(e), id(e.cache.block_table))
              for e in orch.engines}
    in_flight = sum(e.num_active for e in orch.engines)
    orch._recompose_preserving(2.0, drain=True)
    for e in orch.engines:
        prev_id, prev_bt = before[tuple(e.chain.servers)]
        assert id(e) == prev_id and id(e.cache.block_table) == prev_bt
    assert sum(e.num_active for e in orch.engines) == in_flight
    orch.drain()
    assert all(r.state == State.DONE for r in reqs)
    for r in reqs[:3]:
        assert r.output == greedy_rollout(model, params, r.prompt, 6)
    snap = orch.metrics.snapshot().as_dict()
    assert "orch.free_pages" in snap
    assert snap["engine.jit_clears"] == 0
    assert snap["orch.batch_occupancy"]["count"] > 0


def test_page_accounting_round_trips_s_c():
    """pages <-> s_c is exact: a full slot's pages occupy exactly the s_c
    gigabytes GCA granted for that slot."""
    from repro.serving import PAGE_SIZE, PageAccounting

    cfg = get("qwen3-8b")
    spec = service_spec_for(cfg, max_seq=4096)
    acct = PageAccounting.from_spec(spec, max_seq=4096)
    assert acct.page_size == PAGE_SIZE
    assert acct.pages_per_slot == 4096 // PAGE_SIZE
    assert acct.gb_for_pages(acct.pages_per_slot) == spec.cache_size_gb
    assert acct.gb_for_pages(acct.pages_for_slots(3)) \
        == pytest.approx(3 * spec.cache_size_gb)
    assert acct.pages_for_tokens(1) == 1
    assert acct.pages_for_tokens(PAGE_SIZE) == 1
    assert acct.pages_for_tokens(PAGE_SIZE + 1) == 2


def test_slot_cache_active_slots_tracks_set(tiny):
    from repro.serving import SlotCache

    cfg, model, params = tiny
    sc = SlotCache(model, capacity=4, max_seq=32)
    a, b = sc.acquire(), sc.acquire()
    assert sorted([a, b]) == sc.active_slots
    sc.release(a)
    assert sc.active_slots == [b]
    sc.release(b)
    assert sc.active_slots == []


def test_prefill_jit_cache_is_bounded(tiny):
    """Admitting many distinct prompt-length buckets never holds more than
    PREFILL_BUCKET_LIMIT live prefill specializations."""
    from repro.serving.engine import PREFILL_BUCKET_LIMIT

    eng = _paged_engine(tiny, capacity=1, max_seq=2048)
    for plen in (3, 17, 33, 65, 129, 257, 513, 1025, 1500, 2000):
        r = _mk_request(plen, plen, 1)
        assert eng.admit(r)
        assert eng.prefill_bucket_count <= PREFILL_BUCKET_LIMIT
        while eng.requests:
            eng.step()
    assert eng.prefill_bucket_count <= PREFILL_BUCKET_LIMIT


def test_service_spec_and_tau_estimates():
    cfg = get("qwen3-8b")
    spec = service_spec_for(cfg, max_seq=32768, tp_degree=16)
    # qwen3-8b layer ~ 193M params -> ~0.386 GB bf16 /16 ~ 0.024 GB
    assert 0.01 < spec.block_size_gb < 0.05
    # KV 2*8*128*2B * 32768 / 16 ~ 0.0168 GB
    assert 0.005 < spec.cache_size_gb < 0.05
    tau = tau_estimates(cfg, mean_in_tokens=2000, mean_out_tokens=20)
    assert 0.0 < tau < 1.0
    # hybrid: windowed layers shrink s_c; ssm: state-only
    hy = service_spec_for(get("hymba-1.5b"), max_seq=32768)
    full_kv = get("hymba-1.5b").kv_bytes_per_token_per_layer() * 32768 / (1024.0 ** 3)
    assert hy.cache_size_gb < 0.35 * full_kv
    xl = service_spec_for(get("xlstm-350m"), max_seq=524288)
    assert xl.cache_size_gb < 0.01  # state, not KV: tiny and S-independent
