"""Live spans and counters of the served path: ``repro.obs.SpanRecorder`` on
its own, inside the paged chain engine and the orchestrator, the
``engine.*`` counters, and the named scopes of the decode step program."""
import json
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import Server
from repro.core.chains import Chain
from repro.models import Model
from repro.obs import MetricsRegistry, SpanRecorder, export_chrome_trace
from repro.serving import (Orchestrator, OrchestratorConfig, PagedChainEngine,
                           Request, State, service_spec_for)
from repro.serving import engine as engine_mod
from repro.serving.kv_cache import kv_span


@pytest.fixture(scope="module")
def tiny():
    cfg = get("stablelm-1.6b").reduced(num_layers=2, vocab_size=128,
                                       attn_chunk_threshold=1 << 30)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _request(rid, prompt_len, n_new):
    rng = np.random.default_rng(rid)
    return Request(rid=rid, prompt=rng.integers(1, 100, prompt_len)
                   .astype(np.int32), max_new_tokens=n_new)


def _engine(tiny, capacity=3, max_seq=64, page_size=4):
    cfg, model, params = tiny
    return PagedChainEngine(model, params,
                            Chain(("s0",), (cfg.num_layers,), 1.0),
                            capacity, max_seq, page_size=page_size)


def _serve(eng, reqs):
    pending = list(reqs)
    while pending or eng.requests:
        while pending and eng.has_free_slot and eng.admit(pending[0]):
            pending.pop(0)
        eng.step()
    return [r.output for r in reqs]


def _by_id(rec):
    return {s.sid: s for s in rec.spans}


# -- the recorder alone --------------------------------------------------------

def test_spans_nest_with_parent_ids():
    rec = SpanRecorder()
    top = rec.begin("engine.step", "engine", engine=1)
    rec.begin("engine.plan", "engine")
    rec.phase("engine.launch", "engine")
    inner = rec.begin("inner", "engine")
    rec.end(top, rows=3)                  # closes launch and inner as well
    rec.end(inner)                        # already closed: nothing happens
    with rec.span("orch.round", "orch") as rid_round:
        pass
    spans = _by_id(rec)
    assert [s.name for s in rec.spans] == [
        "engine.plan", "inner", "engine.launch", "engine.step", "orch.round"]
    names = {s.name: s for s in rec.spans}
    assert names["engine.step"].parent is None
    assert names["engine.step"].args == {"engine": 1, "rows": 3}
    assert names["engine.plan"].parent == top
    assert names["engine.launch"].parent == top
    assert names["inner"].parent == names["engine.launch"].sid
    assert spans[rid_round].parent is None
    for s in rec.spans:
        assert s.t1 >= s.t0
        if s.parent is not None:
            p = spans[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1


def test_queue_span_closes_at_admission_and_exports(tmp_path):
    rec = SpanRecorder()
    rec.queued(7)
    rec.dequeued(7)
    rec.dequeued(8)                       # never queued: no span
    (q,) = rec.spans
    assert (q.name, q.cat, q.args, q.tid) == ("req.queue", "request",
                                              {"rid": 7}, 7)
    sid = rec.begin("orch.round", "orch")
    rec.end(sid)
    trace = rec.run_trace()
    trace.self_check()
    path = tmp_path / "spans.json"
    export_chrome_trace(trace, str(path))
    doc = json.loads(path.read_text())
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in events] == ["req.queue", "orch.round"]
    assert events[1]["args"] == {"id": sid, "parent": None}


# -- inside the engines and the orchestrator -----------------------------------

def test_greedy_streams_identical_with_recorder_on_and_off(tiny):
    reqs = [(i, n, 9) for i, n in enumerate((3, 6, 11, 17))]
    off = _engine(tiny)
    on = _engine(tiny)
    on.recorder = rec = SpanRecorder()
    out_off = _serve(off, [_request(*r) for r in reqs])
    out_on = _serve(on, [_request(*r) for r in reqs])
    assert out_on == out_off
    assert off.recorder is None
    spans = _by_id(rec)
    steps = [s for s in rec.spans if s.name == "engine.step"]
    admits = [s for s in rec.spans if s.name == "engine.admit"]
    assert len(admits) == len(reqs) and steps
    for s in rec.spans:
        kids = [c.name for c in rec.spans if c.parent == s.sid]
        if s.name == "engine.step":
            assert kids == ["engine.plan", "engine.launch", "engine.fetch",
                            "engine.commit"]
            assert s.args["rows"] >= 1 and s.args["pad"] >= 0
        elif s.name == "engine.admit":
            assert kids == ["engine.prefill", "engine.fetch", "engine.write"]
        elif s.parent is not None:
            assert spans[s.parent].name in ("engine.step", "engine.admit")
    for (rid, n, _), a in zip(reqs, admits):
        assert a.args["rid"] == rid and a.args["prompt"] == n
        assert a.args["pad"] == kv_span(n, 4, 64) - n
    assert on.counts["prefill_tokens"] == sum(n for _, n, _ in reqs)


def _one_chain_orchestrator(tiny, max_seq=64, page_size=4):
    cfg, model, params = tiny
    spec = service_spec_for(cfg, max_seq=max_seq)
    mem = (spec.block_size_gb * cfg.num_layers
           + spec.cache_size_gb * cfg.num_layers * 4)
    return Orchestrator(
        [Server("s0", mem, 0.05, 0.02)], spec, model, params, 0.5,
        OrchestratorConfig(max_seq=max_seq,
                           engine_factory=partial(PagedChainEngine,
                                                  page_size=page_size)))


def test_counters_match_a_hand_count(tiny, monkeypatch):
    """Prompts of 3, 5 and 9 tokens at 4-token pages prefill 4, 8 and 16
    positions: 17 tokens and 11 of padding.  The first request decodes at
    page counts 1, 2 and 4: with room for two decode shapes, the third
    clears the step program's cache once."""
    monkeypatch.setattr(engine_mod, "DECODE_SHAPE_LIMIT", 2)
    orch = _one_chain_orchestrator(tiny)
    assert len(orch.engines) == 1
    orch.metrics = MetricsRegistry()
    orch.recorder = rec = SpanRecorder()
    first = _request(0, 3, 10)            # decodes at lengths 3..11
    orch.submit(first)
    orch.drain()
    assert first.state == State.DONE
    for r in (_request(1, 5, 1), _request(2, 9, 1)):
        orch.submit(r)                    # done at admission
    orch.step()                           # publication is once a round
    snap = orch.metrics.snapshot().as_dict()
    assert snap["engine.prefill_tokens"] == 3 + 5 + 9
    assert snap["engine.prefill_pad_tokens"] == 1 + 3 + 7
    assert snap["engine.jit_clears"] == 1
    assert snap["engine.preemptions"] == 0
    assert snap["engine.pages_in_use"] == 0
    admits = [s for s in rec.spans if s.name == "engine.admit"]
    assert sum(s.args["pad"] for s in admits) == \
        snap["engine.prefill_pad_tokens"]
    # every request's spans carry its id: queued, dispatched, admitted
    for rid in (0, 1, 2):
        names = {s.name for s in rec.spans if s.args.get("rid") == rid}
        assert names == {"req.queue", "orch.dispatch", "engine.admit"}
    rounds = [s for s in rec.spans if s.name == "orch.round"]
    steps = [s for s in rec.spans if s.name == "engine.step"]
    assert rounds and len(steps) == 9
    spans = _by_id(rec)
    assert all(spans[s.parent].name == "orch.round" for s in steps)
    assert all(s.args["engine"] == 0 for s in steps)


def test_detached_recorder_records_nothing(tiny):
    orch = _one_chain_orchestrator(tiny)
    rec = SpanRecorder()
    orch.recorder = rec
    orch.recorder = None
    assert all(e.recorder is None for e in orch.engines)
    orch.submit(_request(0, 5, 3))
    orch.drain()
    assert rec.spans == []
    # a recompose hands the recorder to the engines it makes
    orch.recorder = rec
    orch._recompose_preserving(1.0)
    assert all(e.recorder is rec for e in orch.engines)


def test_step_program_carries_named_scopes(tiny):
    """The decode step's operations carry the ``kv_gather``,
    ``model_decode`` and ``kv_scatter`` scopes in their metadata, and the
    program keeps the name a device trace reads (``jit__step_impl``)."""
    eng = _engine(tiny)
    assert eng.admit(_request(0, 5, 4))
    active, view = eng._plan()
    lowered = eng._step_jit.lower(eng.params, eng.cache.leaves, *(
        jnp.asarray(view[k]) for k in ("page_ids", "slot_idx", "tokens",
                                       "lengths", "write_page", "write_off")))
    text = lowered.compile().as_text()
    assert text.startswith("HloModule jit__step_impl")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("kv_gather", "model_decode", "kv_scatter"):
        assert any(f"jit(_step_impl)/{scope}/" in n for n in names), scope
