"""Property-based harness for the continuous-batching scheduler.

Randomized workloads (prompt lengths, max_new_tokens, slot counts, block
pressure) drive :class:`repro.serve.engine.Engine` tick by tick while
checking the scheduler invariants:

  * at most one live request per slot, and no request on two slots;
  * no KV block owned by two slots / leaked (``KVBlockManager.check()``);
  * token conservation: every request gets exactly ``max_new_tokens``
    and ``Engine.stats()`` counts them exactly, under slot recycling;
  * admission is strict FIFO under equal priority;
  * every greedy token, batched or solo, scores within ``LOGIT_TOL`` of
    the best token under the model's plain full forward.

Runs under real ``hypothesis`` when installed and under the bundled
fallback engine (``tests/_hypothesis_compat``) otherwise.
"""
import jax
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.models.config import ModelConfig
from repro.models.model import init_params
from repro.parallel.api import ParallelConfig
from repro.launch.mesh import make_mesh
from repro.serve.engine import Engine, Request
from repro.serve.reference import served_token_margins

TINY = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=160, vocab=256,
                   head_dim=16, act="swiglu")
MAX_LEN = 32
CHUNK = 8
# Engine and reference run the same bf16 model at different shapes
# (paged chunked prefill in a batch vs one full pass), so activations
# round differently.  bf16 keeps 8 mantissa bits: one rounding moves a
# unit-scale value by at most 2^-8 ~ 0.004, and the few roundings that
# differ over TINY's two layers move its unit-scale logits well under
# 0.05.  A token the reference does not favour sits a median ~2.7 below
# its best, so this tolerance still rejects a wrong choice.
LOGIT_TOL = 0.05

_CTX = {}


def _ctx():
    """One params/mesh/bundle shared by every engine in this module so
    the jitted serve step compiles once per (B, S) shape, not per
    hypothesis example."""
    if not _CTX:
        mesh = make_mesh((1, 1), ("data", "model"))
        pc = ParallelConfig(dp=1, tp=1)
        params, _ = init_params(TINY, pc, jax.random.PRNGKey(0))
        eng = Engine(TINY, pc, mesh, params, batch_slots=1,
                     max_len=MAX_LEN, prefill_chunk=CHUNK)
        _CTX.update(mesh=mesh, pc=pc, params=params, bundle=eng.bundle)
    return _CTX


def _engine(batch_slots, n_blocks=None, block_size=4, **kw):
    c = _ctx()
    return Engine(TINY, c["pc"], c["mesh"], c["params"],
                  batch_slots=batch_slots, max_len=MAX_LEN,
                  prefill_chunk=CHUNK, block_size=block_size,
                  n_blocks=n_blocks, bundle=c["bundle"], **kw)


def _requests(seed, lengths, max_new):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, TINY.vocab, n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in zip(lengths, max_new)]


def _record_admissions(eng, admitted):
    """Wrap ``_admit`` to log the FIFO-pop order of admitted uids."""
    orig = eng._admit

    def wrapped():
        before = list(eng.queue)
        orig()
        n = len(before) - len(eng.queue)
        admitted.extend(r.uid for r in before[:n])
    eng._admit = wrapped


@settings(max_examples=12, deadline=None)
@given(batch_slots=st.integers(1, 3), tight=st.booleans(),
       data=st.data())
def test_scheduler_invariants(batch_slots, tight, data):
    n_req = data.draw(st.integers(1, 6))
    lengths = [data.draw(st.integers(1, 20)) for _ in range(n_req)]
    max_new = [data.draw(st.integers(1, 5)) for _ in range(n_req)]
    seed = data.draw(st.integers(0, 10**6))
    nb_max = -(-MAX_LEN // 4)
    # tight: roughly one resident request's worth of blocks -> queueing
    # and slot recycling under block pressure
    n_blocks = 1 + nb_max if tight else None
    eng = _engine(batch_slots, n_blocks=n_blocks)
    admitted = []
    _record_admissions(eng, admitted)
    reqs = _requests(seed, lengths, max_new)
    for r in reqs:
        eng.submit(r)

    guard = 0
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        guard += 1
        assert guard < 10_000, "scheduler did not make progress"
        # one request per slot, and never the same request on two slots
        live = [s.req.uid for s in eng.slots if s is not None]
        assert len(live) == len(set(live))
        # block-table consistency: no sharing, no leaks, rows in sync
        for m in eng.kv:
            m.check()
        # a live row never outgrows its reserved footprint
        for b, s in enumerate(eng.slots):
            if s is not None:
                total = len(s.req.prompt) + s.req.max_new_tokens
                assert s.fed <= total

    # FIFO admission: uids are assigned in submit order, so admission
    # order must be exactly the submission order
    assert admitted == [r.uid for r in reqs]

    # token conservation + exact stats under slot recycling
    for r in reqs:
        assert r.done and len(r.out_tokens) == r.max_new_tokens
        assert all(0 <= t < TINY.vocab for t in r.out_tokens)
    st_ = eng.stats()
    assert st_["requests"] == n_req
    assert st_["tokens"] == sum(len(r.out_tokens) for r in reqs)
    assert st_["tokens"] == sum(max_new)
    assert st_["live"] == 0 and st_["queued"] == 0
    assert st_["ticks"] >= st_["prefill_ticks"] >= 0
    assert st_["ttft_us"]["count"] == n_req
    assert st_["request_latency_us"]["count"] == n_req
    for m in eng.kv:
        assert m.n_used == 0
        m.check()


@settings(max_examples=6, deadline=None)
@given(batch_slots=st.integers(2, 3), data=st.data())
def test_scheduler_greedy_matches_solo(batch_slots, data):
    """Batched and solo greedy runs of each request both pick, at every
    step, a token within LOGIT_TOL of the reference's best (they may
    still differ from each other where two tokens tie within rounding)."""
    n_req = data.draw(st.integers(2, 4))
    lengths = [data.draw(st.integers(1, 16)) for _ in range(n_req)]
    seed = data.draw(st.integers(0, 10**6))
    reqs = _requests(seed, lengths, [4] * n_req)
    _engine(batch_slots).generate(reqs)
    solo = _engine(1)
    params = _ctx()["params"]
    for r in reqs:
        r2 = Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens)
        solo.generate([r2])
        for got in (r, r2):
            margins = served_token_margins(TINY, params, got.prompt,
                                           got.out_tokens)
            assert margins.max() <= LOGIT_TOL, \
                (len(r.prompt), r.out_tokens, r2.out_tokens, margins)


def test_stats_wellformed_before_any_request_completes():
    """Satellite regression: Engine.stats() must return a well-formed,
    JSON-serializable snapshot with zeroed counts on a fresh engine and
    mid-flight -- the serving benchmark snapshots stats() around every
    QPS level, including before the first request finishes."""
    import json

    eng = _engine(2)
    s0 = eng.stats()
    json.dumps(s0)                       # plain JSON, no exception
    assert s0["requests"] == 0 and s0["tokens"] == 0
    assert s0["ticks"] == 0 and s0["prefill_ticks"] == 0
    assert s0["queued"] == 0 and s0["live"] == 0
    for hist in (s0["ttft_us"], s0["request_latency_us"]):
        assert hist["count"] == 0 and hist["sum"] == 0.0
        assert hist["mean"] is None and hist["p99"] is None

    # submitted but not yet stepped: the submission counter and gauges
    # move, finished-request distributions stay empty
    [r] = _requests(3, [6], [4])
    eng.submit(r)
    s1 = eng.stats()
    json.dumps(s1)
    assert s1["queued"] + s1["live"] == 1
    assert s1["requests"] == 1
    assert s1["request_latency_us"]["count"] == 0

    # one tick in (request still unfinished): still well-formed
    eng.step()
    s2 = eng.stats()
    json.dumps(s2)
    assert s2["ticks"] >= 1
    assert s2["request_latency_us"]["count"] == 0
